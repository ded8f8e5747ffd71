"""``ssm_scan_device_ms`` — compiled step: device time per traced step of
the operations under the program's scope ``ssm_scan`` (``transformer/
ssm.py``: the chunked scan, its kernels and what XLA runs around them),
forward, re-run and backward together (``scope_reduce.scope_ms``)."""
import scope_reduce

SCOPES = ("ssm_scan",)


def read(run):
    return scope_reduce.scope_ms(run, SCOPES)
