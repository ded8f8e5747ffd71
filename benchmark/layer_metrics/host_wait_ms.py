"""``host_wait_ms`` — host dispatch: the host seconds ``StepAttribution``
billed to ``runahead_stall`` over the window, per step: the time the
training thread was blocked on the device, in the ring's own wait
(``step.backpressure``) or held back by the runtime inside a call that
enqueues a program (``EnqueueSplit``).  With ``dispatch_ms`` it makes up
the step.  Says nothing in a run with no device plane."""


def read(run):
    trace, attribution = run.get("trace"), run.get("attribution")
    if not trace or not trace.get("devices") or not attribution \
            or not attribution.get("steps"):
        return None
    phases = attribution["phases_s"]
    if "runahead_stall" not in phases:
        return None
    return phases["runahead_stall"] / attribution["steps"] * 1e3
