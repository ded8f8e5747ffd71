"""``mamba_device_ms`` — compiled step: device time per traced step of the
operations under the program's scope ``mamba_mixer`` (``transformer/
hybrid.py``: the mixers of the Mamba-2 layers, the five ``ssm_*`` scopes),
forward, re-run and backward together (``scope_reduce.scope_ms``)."""
import scope_reduce

SCOPES = ("mamba_mixer",)


def read(run):
    return scope_reduce.scope_ms(run, SCOPES)
