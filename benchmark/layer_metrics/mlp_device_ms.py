"""``mlp_device_ms`` — compiled step: device time per traced step of the
operations under the program's scope ``gated_mlp`` (``transformer/
hybrid.py``: every layer's gated feed-forward), forward, re-run and
backward together (``scope_reduce.scope_ms``)."""
import scope_reduce

SCOPES = ("gated_mlp",)


def read(run):
    return scope_reduce.scope_ms(run, SCOPES)
