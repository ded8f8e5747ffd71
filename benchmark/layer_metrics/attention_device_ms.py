"""``attention_device_ms`` — compiled step: device time per traced step of
the operations under the program's scope ``attention`` (``transformer/
hybrid.py``: the mixers of the attention layers), forward, re-run and
backward together (``scope_reduce.scope_ms``)."""
import scope_reduce

SCOPES = ("attention",)


def read(run):
    return scope_reduce.scope_ms(run, SCOPES)
