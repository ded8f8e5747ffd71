"""``scoped_device_share`` — device: the share of the device's busy time
that fell under any ``jax.named_scope`` of the program (a block's name,
``loss``, ``optimizer_update``, ``grad_reduce``): the tracing's own
coverage.  Says nothing where no operation is scoped (a commit before the
scopes)."""
import scope_reduce


def read(run):
    reduced = scope_reduce.of_run(run)
    times = scope_reduce.mean_device_time(reduced) if reduced else None
    if not times or not times["scoped"] or not times["busy"]:
        return None
    return 100.0 * times["scoped"] / times["busy"]
