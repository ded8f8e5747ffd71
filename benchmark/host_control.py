#!/usr/bin/env python3
"""The host's time per training step, taken from outside the program: the
control for ``dispatch_ms``, and what armed telemetry costs a step.

    python benchmark/host_control.py --workload <name> --seed <n>

A normal run keeps as many steps in flight as the runtime allows, and the
runtime then holds a call of the next step back for a whole step *inside*
``trainer.step``; the program has to tell that wait from its own work
(``telemetry.EnqueueSplit``), and ``dispatch_ms`` reads what it decided.
Here nothing can be held back: the cell's own program is stepped with at
most two steps in flight (the loop waits for step ``n - 1`` once ``step``
has returned for step ``n``), so the time ``step`` takes is host work
alone, and this file takes it with the host's clock around the call.
Blocks of ``--steps`` steps alternate, telemetry disarmed then armed, the
profiler never on, and one JSON line says per block:

- ``host_ms_per_step``: ``disarmed`` and ``armed``.  Their difference is
  what armed telemetry costs a step (``armed_cost_ms_per_step``, between
  the medians over the rounds);
- ``dispatch_ms``: what ``StepAttribution`` billed to ``dispatch`` and
  ``h2d_transfer`` per step in the armed blocks, the two phases the
  metric of that name reads.  A normal ``--trace 1`` run of the cell has
  to read the same within 25% or 0.3 ms (ISSUE 26), or the split is wrong;
- ``stall_ms``: what it billed to ``runahead_stall``: here only the
  flush that ends a block, a step's time over ``--steps``.

The benchmark's own runs do not run this; it needs the chip at the cell's
size.  ``--rehearsal`` runs it at the configuration's ``rehearsal_size``
on any backend and is no measurement.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as harness  # noqa: E402


def stepped(program, feed, steps):
    """``(host_s, wait_s)`` per step over ``steps`` steps with at most
    two in flight: the time inside ``step`` and the time waiting, after
    it, for the step before."""
    program.flush()
    host = wait = 0.0
    before = None
    for i in range(steps):
        x, y = feed[i % len(feed)]
        t0 = time.perf_counter()
        loss = program.step(x, y)
        t1 = time.perf_counter()
        if before is not None:
            before.block_until_ready()
        host += t1 - t0
        wait += time.perf_counter() - t1
        before = loss
    program.flush()
    return host / steps, wait / steps


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--steps", type=int, default=100)
    parser.add_argument("--rounds", type=int, default=3)
    parser.add_argument("--rehearsal", action="store_true")
    args = parser.parse_args(argv)

    manifest = harness.load_json(harness.REPO, "BENCHMARK.json")
    cell, config, traffic, _, _ = harness.resolve(manifest, args.workload)
    chips = int(cell["chips"])
    sys.path.insert(0, harness.REPO)

    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.parallel import make_mesh

    devices = jax.devices()
    if not args.rehearsal and devices[0].platform != "tpu":
        raise harness.Refused("JAX found platform %r, not a TPU"
                              % devices[0].platform)
    mx.base.use_compilation_cache()
    size = harness.cell_size(config, args.rehearsal)
    family = harness.load_module("families", config["family"])
    kind = harness.load_module("traffic_kinds", traffic["kind"])
    mesh = make_mesh((chips,), ("data",), devices[:chips])
    feed = kind.batches(config, size, mesh, args.seed, traffic)
    program = family.build(config, size, mesh, args.seed)
    stepped(program, feed, harness.CHECKED_STEPS + harness.WARM_STEPS)

    line = {"workload": args.workload, "seed": args.seed,
            "device": devices[0].device_kind, "steps": args.steps,
            "host_ms_per_step": {"disarmed": [], "armed": []},
            "wait_ms_per_step": {"disarmed": [], "armed": []},
            "dispatch_ms": [], "stall_ms": []}
    for _ in range(args.rounds):
        for arm in ("disarmed", "armed"):
            if arm == "armed":
                mx.telemetry.enable()
                attribution = mx.telemetry.attribution()
                before = attribution.snapshot()
            host, wait = stepped(program, feed, args.steps)
            line["host_ms_per_step"][arm].append(host * 1e3)
            line["wait_ms_per_step"][arm].append(wait * 1e3)
            if arm == "armed":
                attribution.flush_window()
                after = attribution.snapshot()
                mx.telemetry.disable()
                steps = after["steps"] - before["steps"]
                per_step = {k: (v - before["phases_s"].get(k, 0.0))
                            / steps * 1e3
                            for k, v in after["phases_s"].items()}
                line["dispatch_ms"].append(
                    per_step.get("dispatch", 0.0)
                    + per_step.get("h2d_transfer", 0.0))
                line["stall_ms"].append(per_step.get("runahead_stall", 0.0))
    line["armed_cost_ms_per_step"] = (
        statistics.median(line["host_ms_per_step"]["armed"])
        - statistics.median(line["host_ms_per_step"]["disarmed"]))
    if args.rehearsal:
        line["rehearsal"] = True
    program.close()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
