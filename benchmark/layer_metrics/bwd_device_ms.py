"""``bwd_device_ms`` — compiled step: device time per traced step of the
operations scoped backward (``transpose(`` in the ``op_name``; a
rematerialised forward counts here), self time, averaged over the chips
(``scope_reduce.device_time``)."""
import scope_reduce


def read(run):
    return scope_reduce.per_step_ms(run, "backward")
