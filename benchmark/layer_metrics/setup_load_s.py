"""``setup_load_s`` — compiled step: seconds of set-up in the backend's
compiler or, where the persistent cache hit, retrieving the executable,
every program of the process together (``telemetry.compiles`` when
telemetry was armed as set-up ended)."""
import compile_counters


def read(run):
    counters = compile_counters.at_armed(run)
    if counters is None:
        return None
    return counters["backend_s"]
