"""``mtp_device_ms`` — compiled step: device time per traced step of the
operations under the program"s scope ``mtp_module`` (``transformer/hybrid.py``:
the next-next-token prediction module whole: its norms and ``eh_proj``, its
own attention, experts and head, which the metrics of those scopes count
too), forward, re-run and backward together
(``scope_reduce.scope_ms``)."""
import scope_reduce

SCOPES = ("mtp_module",)


def read(run):
    return scope_reduce.scope_ms(run, SCOPES)
