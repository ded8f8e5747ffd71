"""``fused_update_us`` — Pallas kernels (``ops/fused_optimizer.py``): the
time of the fused SGD-momentum update, as the summed duration of the
trace's events that carry the kernel's ``name=``, per traced step and
chip.  Finds nothing, and says nothing, where the step has no such kernel.

No share of a roofline is made of it in the ResNet cells: the kernel sees
only the BatchNorm scales and shifts (53,120 and 9,600 float32 elements),
which XLA keeps in on-chip memory across the call, so that no HBM stream
bounds it (PERF.md, Findings of PR 25)."""

KERNEL = "_fused_sgd_mom_kernel"


def read(run):
    trace = run.get("trace")
    if not trace or not trace["devices"] or not run.get("traced_steps"):
        return None
    per_chip = []
    for ops in trace["devices"].values():
        events = [e for e in ops if KERNEL in e[0]]
        if not events:
            return None
        per_chip.append(sum(end - start for _, start, end in events))
    return sum(per_chip) / len(per_chip) / run["traced_steps"] / 1e3
