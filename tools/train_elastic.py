#!/usr/bin/env python
"""Elastic ZeRO-1 training driver: worker job + supervisor CLI.

Two modes (docs/elastic.md):

- **worker** (default): run ONE SPMD training job over the given rank
  set — a ``DataParallelTrainer(zero=1)`` on a ``len(ranks)``-way
  virtual CPU mesh (one host process serving K ranks, exactly how a TPU
  pod slice runs one process per host).  The worker pins
  ``JAX_PLATFORMS=cpu`` itself: this driver is the elastic tier's
  CPU-mesh harness and never runs on the chip.  Each global step, every rank's
  liveness is published to the work directory (``hb-<rank>.json``)
  around its ``train.step`` chaos probe, the step trains, and a
  shard-parallel checkpoint commits every ``--checkpoint-every`` steps.
  Deterministic by construction: the batch for global step *s* is a
  pure function of ``(seed, s)`` — independent of fleet size and of
  where a resume picked up — so two same-size runs from the same
  checkpoint are bitwise-identical.  SIGTERM yields: finish the step,
  checkpoint, exit ``rc=3`` (the supervisor's grow point).

- ``--supervise``: run the :class:`ElasticSupervisor` around that
  worker: launch at ``--ranks``, watch heartbeats, shrink on rank
  death / grow on a join announcement (``--announce``), audit every
  decision (``<workdir>/audit/audit-<seq>.json``).

Chaos: the worker arms ``MXTPU_CHAOS`` from its environment; the
supervisor forwards ``--chaos`` to the FIRST launch only, so the fault
that killed the fleet is not re-armed on the respawn.  The ``train.step``
probe fires once per (step, rank) in rank order with
``count = (step-1)*world + position + 1`` — a kill at rank *r*'s probe
models host *r* dying: earlier ranks completed the probe, later ranks
never reached it, and the supervisor's victim rule names *r* uniquely.

Usage (the headline chaos scenario, tests/test_elastic.py)::

    python tools/train_elastic.py --supervise --workdir /tmp/run \\
        --ranks 0,1,2,3 --steps 16 --batch 24 --checkpoint-every 1 \\
        --chaos "train.step:47:kill"      # rank 2 dies at step 12

    python tools/train_elastic.py --workdir /tmp/run --announce 2
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def _parse_ranks(spec):
    return sorted(int(r) for r in str(spec).split(",") if r != "")


def batch_for_step(seed, step, batch, in_dim, classes):
    """The global batch for step ``step`` — a pure function of
    (seed, step), so every fleet size and every resume sees the same
    bytes.  numpy only (callable before jax exists)."""
    import numpy as np
    rng = np.random.RandomState((int(seed) * 1000003 + int(step))
                                % (2 ** 31 - 1))
    x = rng.rand(batch, in_dim).astype(np.float32)
    y = rng.randint(0, classes, batch).astype(np.int64)
    return x, y


def run_worker(args):
    ranks = _parse_ranks(args.ranks)
    world = len(ranks)
    # the mesh needs exactly `world` virtual CPU devices; pin them
    # BEFORE jax imports (the conftest.py discipline)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=%d" % world)

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import DataParallelTrainer, make_mesh
    from mxnet_tpu.resilience import chaos, supervisor as sup
    import jax

    chaos.install_from_env()
    workdir = args.workdir
    os.makedirs(workdir, exist_ok=True)

    stop = {"yield": False}

    def _on_term(signum, frame):
        # graceful yield: finish the current step, checkpoint, exit 3
        stop["yield"] = True

    signal.signal(signal.SIGTERM, _on_term)

    mx.random.seed(args.seed)
    np.random.seed(args.seed)
    net = gluon.nn.HybridSequential()
    for h in (int(x) for x in str(args.hidden).split(",") if x):
        net.add(gluon.nn.Dense(h, activation="relu"))
    net.add(gluon.nn.Dense(args.classes))
    net.initialize(mx.init.Xavier())
    mesh = make_mesh((world,), ("data",), jax.devices()[:world])
    trainer = DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": args.lr, "momentum": args.momentum},
        mesh=mesh, zero=1)

    from mxnet_tpu.resilience import checkpoint as ckpt
    start_step = 0
    if args.resume and ckpt.latest_sharded_checkpoint(workdir):
        cursor = trainer.restore_checkpoint(workdir)
        start_step = int(cursor["step"])
        print("RESUMED step=%d world=%d" % (start_step, world),
              flush=True)

    for s in range(start_step + 1, args.steps + 1):
        # per-rank liveness around the train.step probe, in rank order:
        # a kill at rank r's probe leaves r as the unique rank that
        # entered step s without completing it (the supervisor's victim
        # rule); later ranks never enter s
        for pos, r in enumerate(ranks):
            sup.write_heartbeat(workdir, r, enter_step=s,
                                done_step=s - 1, trained_step=s - 1)
            chaos.maybe_inject("train.step",
                               (s - 1) * world + pos + 1, ctx=(r, s))
            sup.write_heartbeat(workdir, r, enter_step=s, done_step=s,
                                trained_step=s - 1)
        x, y = batch_for_step(args.seed, s, args.batch, args.in_dim,
                              args.classes)
        trainer.step(mx.nd.array(x), mx.nd.array(y))
        trainer.flush()
        if args.checkpoint_every and s % args.checkpoint_every == 0:
            trainer.save_checkpoint(workdir, epoch=0, nbatch=s - 1,
                                    keep=args.checkpoint_keep)
        for r in ranks:
            sup.write_heartbeat(workdir, r, enter_step=s, done_step=s,
                                trained_step=s)
        if stop["yield"] and s < args.steps:
            trainer.save_checkpoint(workdir, epoch=0, nbatch=s - 1,
                                    keep=args.checkpoint_keep)
            print("YIELD step=%d" % s, flush=True)
            return sup.YIELD_EXIT_CODE

    # final checkpoint + params blob for bitwise comparisons
    trainer.save_checkpoint(workdir, epoch=0, nbatch=args.steps - 1,
                            keep=args.checkpoint_keep)
    if args.out:
        blob = b"".join(
            np.asarray(p.data()._data).tobytes()
            for p in trainer._params_by_name.values())
        with open(args.out + ".tmp", "wb") as f:
            f.write(blob)
        os.replace(args.out + ".tmp", args.out)
    print("DONE step=%d world=%d" % (trainer._step_count, world),
          flush=True)
    return 0


def run_supervisor(args):
    from mxnet_tpu.resilience.supervisor import ElasticSupervisor
    workdir = args.workdir
    os.makedirs(workdir, exist_ok=True)

    def launch(ranks, resume, extra_env):
        import subprocess
        cmd = [sys.executable, os.path.abspath(__file__),
               "--workdir", workdir,
               "--ranks", ",".join(str(r) for r in ranks),
               "--steps", str(args.steps),
               "--batch", str(args.batch),
               "--in-dim", str(args.in_dim),
               "--classes", str(args.classes),
               "--hidden", args.hidden,
               "--seed", str(args.seed),
               "--lr", str(args.lr),
               "--momentum", str(args.momentum),
               "--checkpoint-every", str(args.checkpoint_every),
               "--checkpoint-keep", str(args.checkpoint_keep)]
        if resume:
            cmd.append("--resume")
        if args.out:
            cmd += ["--out", args.out]
        env = dict(os.environ)
        env.pop("MXTPU_CHAOS", None)
        env.update(extra_env)
        return subprocess.Popen(cmd, env=env)

    chaos_env = {"MXTPU_CHAOS": args.chaos} if args.chaos else {}
    supervisor = ElasticSupervisor(
        workdir, launch, _parse_ranks(args.ranks),
        min_size=args.min_size, max_restarts=args.max_restarts,
        target_steps=args.steps, chaos_env=chaos_env)
    try:
        decision = supervisor.run()
    except Exception as e:
        print("SUPERVISOR HALTED: %s" % (e,), file=sys.stderr)
        return 4
    print("SUPERVISED %s" % json.dumps(decision, sort_keys=True),
          flush=True)
    return 0


def main(argv=None):
    p = argparse.ArgumentParser(
        description="elastic ZeRO-1 training (worker / supervisor)")
    p.add_argument("--workdir", required=True,
                   help="heartbeats, checkpoints, audit trail")
    p.add_argument("--ranks", default="0",
                   help="comma-separated rank ids (fleet size = count)")
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--batch", type=int, default=24,
                   help="GLOBAL batch (must divide by every fleet size)")
    p.add_argument("--in-dim", type=int, default=16)
    p.add_argument("--classes", type=int, default=10)
    p.add_argument("--hidden", default="32",
                   help="comma-separated hidden layer widths")
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--checkpoint-every", type=int, default=1)
    p.add_argument("--checkpoint-keep", type=int, default=3)
    p.add_argument("--resume", action="store_true")
    p.add_argument("--out", default=None,
                   help="write the final params blob here (bitwise "
                        "comparisons)")
    p.add_argument("--supervise", action="store_true",
                   help="run the elastic supervisor around the worker")
    p.add_argument("--min-size", type=int, default=1)
    p.add_argument("--max-restarts", type=int, default=2)
    p.add_argument("--chaos", default=None,
                   help="MXTPU_CHAOS spec forwarded to the FIRST "
                        "launch only (supervise mode)")
    p.add_argument("--announce", type=int, default=None, metavar="RANK",
                   help="write a join request for RANK and exit (a "
                        "rejoining host announcing itself)")
    args = p.parse_args(argv)
    if args.announce is not None:
        from mxnet_tpu.resilience import supervisor as sup
        os.makedirs(args.workdir, exist_ok=True)
        sup.write_join_request(args.workdir, args.announce)
        print("ANNOUNCED rank=%d" % args.announce, flush=True)
        return 0
    if args.supervise:
        return run_supervisor(args)
    return run_worker(args)


if __name__ == "__main__":
    sys.exit(main())
