"""``moe_routing_device_ms`` — compiled step: device time per traced step of
the operations under the program"s scopes ``moe_router``, ``moe_dispatch`` and
``moe_combine`` (``transformer/moe.py``: scores and choice, the sort and the
gather into the buffer of routed rows, the weighted sum back into the
tokens" rows), what the expert layers spend beside their products, forward,
re-run and backward together (``scope_reduce.scope_ms``)."""
import scope_reduce

SCOPES = ("moe_router", "moe_dispatch", "moe_combine")


def read(run):
    return scope_reduce.scope_ms(run, SCOPES)
