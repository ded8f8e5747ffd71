"""``fwd_device_ms`` — compiled step: device time per traced step of the
operations scoped forward (``jvp(`` in the ``op_name`` without
``transpose(``), self time, averaged over the chips
(``scope_reduce.device_time``)."""
import scope_reduce


def read(run):
    return scope_reduce.per_step_ms(run, "forward")
