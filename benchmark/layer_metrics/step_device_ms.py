"""``step_device_ms`` — compiled step: the time in which an operation ran
on the device (the union of the trace's device-op intervals) per traced
step, averaged over the chips."""
import trace_reduce


def read(run):
    trace = run.get("trace")
    if not trace or not trace["devices"] or not run.get("traced_steps"):
        return None
    busy = [trace_reduce.busy_ns(ops) for ops in trace["devices"].values()]
    if not all(busy):
        return None
    return sum(busy) / len(busy) / run["traced_steps"] / 1e6
