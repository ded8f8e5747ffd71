"""The program's compile counters (``mxnet_tpu/telemetry/compiles.py``) as
the layer metrics read them: what set-up cost (the totals when telemetry
was armed, which ``run.py`` does as set-up ends) and what has been compiled
inside training steps since.  None where the program keeps no such counters
(a commit before them), telemetry was never armed, or the run has no device
plane (a rehearsal on the CPU is no measurement)."""


def _counters(run):
    trace = run.get("trace")
    if not trace or not trace.get("devices") or not run.get("attribution"):
        return None
    import mxnet_tpu as mx
    return getattr(mx.telemetry, "compiles", None)


def at_armed(run):
    counters = _counters(run)
    return counters.at_armed() if counters else None


def since_armed(run):
    counters = _counters(run)
    return counters.since_armed() if counters else None
