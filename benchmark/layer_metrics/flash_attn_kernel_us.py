"""``flash_attn_kernel_us`` — Pallas kernels (``ops/pallas_kernels.py``):
the time of causal attention's flash kernels, as the summed duration of the
trace's events that carry one of the three kernels' ``name=``, per traced
step and chip (18 runs a step in the JoyAI cell: the forward kernel once and
each backward kernel once for six layers; 3 in the Granite cell).  What is
left of ``attention_device_ms`` beside it is XLA around the kernels: the
projections, the rotary positions, delta.  Says nothing where the step has
no such kernel."""
import trace_reduce

KERNELS = ("_fa_kernel", "_fa_dq_kernel", "_fa_dkv_kernel")


def read(run):
    per_chip = trace_reduce.kernel_runs(run, KERNELS)
    if not per_chip:
        return None
    took = sum(ns for runs in per_chip for _, ns in runs)
    return took / len(per_chip) / run["traced_steps"] / 1e3
