"""``hyper_conn_roofline`` — compiled step: the hyper-connections over the
time of whatever computes them.  The least time a chip could take for the
hyper-connections a step needs, ``max(operations / bf16 peak, bytes / HBM
peak)`` of ``peaks.json`` for the forward pass and for the backward pass
each, over the device time a traced step spends under the program's scope
``hyper_connection`` (``hyper_conn_device_ms``: forward, every re-run and
backward together).  The operations and bytes are the family's count from
shapes alone (``hyper_connection_work``), so that a kernel that takes the
``jax.numpy`` spelling's place is read against the same work.  Says nothing
where the trace has no operation under that scope, the device no peaks or
the family no such count."""
import scope_reduce

SCOPES = ("hyper_connection",)


def read(run):
    peaks, family = run.get("peaks"), run.get("family")
    if not peaks or not hasattr(family, "hyper_connection_work"):
        return None
    took_ms = scope_reduce.scope_ms(run, SCOPES)
    if not took_ms:
        return None
    least_s = sum(max(flops / peaks["bf16_flops_per_s"],
                      moved / peaks["hbm_bytes_per_s"])
                  for flops, moved in family.hyper_connection_work(
                      run["config"], run["size"]))
    return 100.0 * least_s / (took_ms / 1e3)
