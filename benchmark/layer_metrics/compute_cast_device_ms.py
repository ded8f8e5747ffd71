"""``compute_cast_device_ms`` — compiled step: device time per traced step of
the operations under the program's scope ``compute_cast``
(``precision._to_compute``, ISSUE 40: the masters' casts to the compute
dtype at the forward boundary, their transposes in the backward pass, the
batch's cast), forward and backward together (``scope_reduce.scope_ms``).
Says nothing where no operation carries the scope: a commit before it, or
a step that makes no such cast (a float32 compute dtype)."""
import scope_reduce

SCOPES = ("compute_cast",)


def read(run):
    return scope_reduce.scope_ms(run, SCOPES)
