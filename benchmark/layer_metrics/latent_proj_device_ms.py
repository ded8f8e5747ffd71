"""``latent_proj_device_ms`` — compiled step: device time per traced step of
the operations under the program"s scopes ``mla_q_proj``, ``mla_kv_proj``,
``mla_rope`` and ``mla_out_proj`` (``transformer/mla.py``: latent attention"s
low-rank paths, rotary turns and output product), what the scope
``attention`` holds beside the scores, forward, re-run and backward together
(``scope_reduce.scope_ms``)."""
import scope_reduce

SCOPES = ("mla_q_proj", "mla_kv_proj", "mla_rope", "mla_out_proj")


def read(run):
    return scope_reduce.scope_ms(run, SCOPES)
