"""``kda_scan_roofline`` — compiled step: the delta rule's recurrence over
the time of whatever computes it.  The least time a chip could take for the
recurrence a step needs, ``max(operations / bf16 peak, bytes / HBM peak)`` of
``peaks.json`` for the forward pass and for the backward pass each, over the
device time a traced step spends under the program's scope ``kda_scan``
(``kda_scan_device_ms``: forward, every re-run and backward together).  The
operations and bytes are the family's count from shapes alone
(``kda_scan_work``): the token-by-token recurrence's, which any chunked form
exceeds, so that a kernel that takes the scan's place is read against the
same work.  Says nothing where the trace has no operation under that scope,
the device no peaks or the family no such count."""
import scope_reduce

SCOPES = ("kda_scan",)


def read(run):
    peaks, family = run.get("peaks"), run.get("family")
    if not peaks or not hasattr(family, "kda_scan_work"):
        return None
    took_ms = scope_reduce.scope_ms(run, SCOPES)
    if not took_ms:
        return None
    least_s = sum(max(flops / peaks["bf16_flops_per_s"],
                      moved / peaks["hbm_bytes_per_s"])
                  for flops, moved in family.kda_scan_work(
                      run["config"], run["size"]))
    return 100.0 * least_s / (took_ms / 1e3)
