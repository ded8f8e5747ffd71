"""``experts_device_ms`` — compiled step: device time per traced step of the
operations under the program"s scope ``sparse_experts`` (``transformer/
hybrid.py``: the sparse-expert feed-forwards, the prediction module"s too:
router, dispatch, the held experts" grouped products, the shared expert,
combine), forward, re-run and backward together
(``scope_reduce.scope_ms``)."""
import scope_reduce

SCOPES = ("sparse_experts",)


def read(run):
    return scope_reduce.scope_ms(run, SCOPES)
