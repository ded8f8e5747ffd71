"""``ssd_scan_kernel_us`` — Pallas kernels (``ops/ssd_kernels.py``): the
time of the Mamba-2 chunked scan's kernel pair, as the summed duration of
the trace's events that carry either kernel's ``name=``, per traced step
and chip (27 runs a step in the Granite cell: forward, re-run and backward
of nine layers).  Says nothing where the step has no such kernel."""
import trace_reduce

KERNELS = ("_ssd_scan_fwd_kernel", "_ssd_scan_bwd_kernel")


def read(run):
    per_chip = trace_reduce.kernel_runs(run, KERNELS)
    if not per_chip:
        return None
    took = sum(ns for runs in per_chip for _, ns in runs)
    return took / len(per_chip) / run["traced_steps"] / 1e3
