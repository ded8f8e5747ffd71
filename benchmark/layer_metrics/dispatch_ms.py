"""``dispatch_ms`` — host dispatch layer (``parallel/trainer.py::step``,
``engine.py``): the host seconds ``StepAttribution`` billed to the phases
``dispatch`` and ``h2d_transfer`` over the window, per step.  Telemetry is
armed in the traced run only."""


def read(run):
    attribution = run.get("attribution")
    if not attribution or not attribution.get("steps"):
        return None
    phases = attribution["phases_s"]
    if "dispatch" not in phases:
        return None
    seconds = phases["dispatch"] + phases.get("h2d_transfer", 0.0)
    return seconds / attribution["steps"] * 1e3
