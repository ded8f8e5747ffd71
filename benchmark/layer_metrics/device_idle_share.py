"""``device_idle_share`` — device: one minus the share of the traced window
(first device operation's start to the last one's end) in which an
operation ran, averaged over the chips."""
import trace_reduce


def read(run):
    trace = run.get("trace")
    if not trace or not trace["devices"]:
        return None
    shares = [trace_reduce.idle_share(ops)
              for ops in trace["devices"].values()]
    if any(s is None for s in shares):
        return None
    return 100.0 * sum(shares) / len(shares)
