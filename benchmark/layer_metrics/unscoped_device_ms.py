"""``unscoped_device_ms`` — device: device time per traced step of the
operations that no ``jax.named_scope`` of the program encloses (casts of
the masters, copies, what a refactor left outside every scope): the busy
time less the scoped, the complement of ``scoped_device_share`` in ms.
Says nothing where no operation is scoped, as that metric does."""
import scope_reduce


def read(run):
    reduced = scope_reduce.of_run(run)
    times = scope_reduce.mean_device_time(reduced) if reduced else None
    if not times or not times["scoped"] or times["busy"] <= times["scoped"]:
        return None
    return (times["busy"] - times["scoped"]) / run["traced_steps"] / 1e6
