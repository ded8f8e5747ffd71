"""``update_device_ms`` — compiled step: device time per traced step of the
operations under the program scope ``optimizer_update`` (the unfused XLA
group, the fused Pallas group and the casts between them), self time,
averaged over the chips.  Says nothing where the program has no such scope
(a commit before it)."""
import scope_reduce


def read(run):
    return scope_reduce.per_step_ms(run, "update")
