"""``head_loss_device_ms`` — compiled step: device time per traced step of
the operations under the program's scope ``lm_head_loss`` (``transformer/
hybrid.py``: the final norm, the head's product and the loss), forward and
backward together (``scope_reduce.scope_ms``)."""
import scope_reduce

SCOPES = ("lm_head_loss",)


def read(run):
    return scope_reduce.scope_ms(run, SCOPES)
