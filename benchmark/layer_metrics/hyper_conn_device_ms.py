"""``hyper_conn_device_ms`` — compiled step: device time per traced step of
the operations under the program's scope ``hyper_connection``
(``transformer/mhc.py``: every sublayer's coefficients, with Sinkhorn, its
read-in and its write-out, and the expansion of the embedding and the merge
before the final norm), forward, re-run and backward together
(``scope_reduce.scope_ms``)."""
import scope_reduce

SCOPES = ("hyper_connection",)


def read(run):
    return scope_reduce.scope_ms(run, SCOPES)
