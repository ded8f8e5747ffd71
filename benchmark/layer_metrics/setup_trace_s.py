"""``setup_trace_s`` — compiled step: seconds of set-up spent tracing
Python to jaxprs and lowering them to MLIR modules, every program of the
process together (``telemetry.compiles`` when telemetry was armed as
set-up ended)."""
import compile_counters


def read(run):
    counters = compile_counters.at_armed(run)
    if counters is None:
        return None
    return counters["trace_s"] + counters["lower_s"]
