"""``programs_per_step`` — host dispatch: device programs that ran per
traced step, as the events of the trace's ``XLA Modules`` line over the
traced steps, averaged over the chips.  One is the compiled step; the
others are small programs the host enqueues beside it (the learning rate,
the step count, the key), each an enqueue of its own."""
import scope_reduce


def read(run):
    reduced = scope_reduce.of_run(run)
    if not reduced:
        return None
    return scope_reduce.programs_per_step(reduced, run["traced_steps"])
