"""``ssd_scan_roofline`` — Pallas kernels (``ops/ssd_kernels.py``): the
scan's kernel pair's share of its roofline.  Over the kernel runs found in
the trace, the sum of each run's roofline time, ``max(operations / bf16
peak, bytes / HBM peak)`` of ``peaks.json``, over the sum of their measured
times.  The operations and bytes of a forward and of a backward run are the
family's count from shapes alone (``ssd_scan_forward_work``,
``ssd_scan_backward_work``): what the scan needs, not what the kernel does.
Says nothing where the trace has no such kernel, the device no peaks or the
family no such count."""
import trace_reduce

WORK = {"_ssd_scan_fwd_kernel": "ssd_scan_forward_work",
        "_ssd_scan_bwd_kernel": "ssd_scan_backward_work"}


def read(run):
    peaks, family = run.get("peaks"), run.get("family")
    if not peaks or not all(hasattr(family, w) for w in WORK.values()):
        return None
    per_chip = trace_reduce.kernel_runs(run, tuple(WORK))
    if not per_chip:
        return None
    least_s = {}
    for kernel, work in WORK.items():
        flops, moved = getattr(family, work)(run["config"], run["size"])
        least_s[kernel] = max(flops / peaks["bf16_flops_per_s"],
                              moved / peaks["hbm_bytes_per_s"])
    runs = [r for chip in per_chip for r in chip]
    took_s = sum(ns for _, ns in runs) / 1e9
    return 100.0 * sum(least_s[kernel] for kernel, _ in runs) / took_s
