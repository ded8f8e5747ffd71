"""``kda_scan_device_ms`` — compiled step: device time per traced step of
the operations under the program's scope ``kda_scan`` (``transformer/
kda.py``: the delta rule chunk by chunk: the running sums of the gate and
their exponentials, the triangular system and its inverse, the products
against the carried state, whatever implements them), forward, every re-run
and backward together (``scope_reduce.scope_ms``)."""
import scope_reduce

SCOPES = ("kda_scan",)


def read(run):
    return scope_reduce.scope_ms(run, SCOPES)
