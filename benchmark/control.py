#!/usr/bin/env python3
"""Readings of the lower-precision control and of the planted faults.

    python benchmark/control.py --workload <name> --seeds 1,2,3

For every seed it makes the cell's first batches, drives the plain
reference through the checked steps, then drives in its place the control
(the reference with float8 operands) and the reference with a fault planted
(half of every batch left out; the state returned unchanged), and prints
one JSON line per seed with the numbers ``correctness.compare`` gives each
of them against the reference.  The limits in ``limits/<workload>.json``
were set between the program's readings (``run.py`` prints them in every
run) and these.  The benchmark's own runs do not run this; it needs the
chip at the cell's size and nothing of the program.  ``--rehearsal`` runs
it at the configuration's ``rehearsal_size`` on any backend.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import correctness  # noqa: E402
import run as harness  # noqa: E402

VARIANTS = (("fp8", "fp8", None),
            ("half_batch", "float32", "half_batch"),
            ("state_unchanged", "float32", "state_unchanged"))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--rehearsal", action="store_true")
    args = parser.parse_args(argv)

    import jax
    from jax.sharding import Mesh

    manifest = harness.load_json(harness.REPO, "BENCHMARK.json")
    cell, config, traffic, _, _ = harness.resolve(manifest, args.workload)
    devices = jax.devices()
    if not args.rehearsal and devices[0].platform != "tpu":
        raise harness.Refused("JAX found platform %r, not a TPU"
                              % devices[0].platform)
    chips = int(cell["chips"])
    size = harness.cell_size(config, args.rehearsal)
    family = harness.load_module("families", config["family"])
    kind = harness.load_module("traffic_kinds", traffic["kind"])
    mesh = Mesh(np.array(devices[:chips]), ("data",))
    for seed in (int(s) for s in args.seeds.split(",")):
        feed = kind.batches(config, size, mesh, seed,
                            traffic)[:harness.CHECKED_STEPS]
        reference = family.reference_readings(config, size, seed, feed)
        line = {"workload": args.workload, "seed": seed,
                "device": devices[0].device_kind,
                "reference_losses": reference["losses"]}
        for name, variant, fault in VARIANTS:
            readings = family.reference_readings(
                config, size, seed, feed, variant=variant, fault=fault)
            line[name] = {k: v[0] for k, v in correctness.compare(
                readings, reference).items()}
        if args.rehearsal:
            line["rehearsal"] = True
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
