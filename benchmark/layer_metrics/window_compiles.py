"""``window_compiles`` — compiled step: executables compiled, or loaded
from the cache, inside a training step (a ``train.step`` span open on the
compiling thread) since telemetry was armed as set-up ended: the window
and the traced steps.  A warmed-up run makes none."""
import compile_counters


def read(run):
    counters = compile_counters.since_armed(run)
    if counters is None:
        return None
    return float(counters["in_span_programs"])
