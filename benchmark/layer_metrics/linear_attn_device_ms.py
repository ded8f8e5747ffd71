"""``linear_attn_device_ms`` — compiled step: device time per traced step of
the operations under the program's scope ``kda_mixer`` (``transformer/
kda.py``: the delta-rule linear-attention mixers whole: projections, short
convolutions, the gate, the chunked scan, the gated output norm and the
output product), forward, re-run and backward together
(``scope_reduce.scope_ms``)."""
import scope_reduce

SCOPES = ("kda_mixer",)


def read(run):
    return scope_reduce.scope_ms(run, SCOPES)
