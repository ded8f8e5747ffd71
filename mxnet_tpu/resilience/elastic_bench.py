"""Host-only elastic-tier micro-bench:
``python -m mxnet_tpu.resilience.elastic_bench``.

Run by ``bench.py``'s ``elastic`` stage as a ``JAX_PLATFORMS=cpu``
subprocess BEFORE backend acquisition (the r05 pattern), so the numbers
stay live when the TPU backend is down.  Prints ONE JSON line:

- ``zero1_modeled_hbm_drop_pct`` — the ZeRO-1 memory win from the
  *runtime* tape (``DataParallelTrainer(zero=1).zero_report`` at the
  pinned ``ZERO1_GEOMETRY``, declared 8-way axis) vs the same trainer's
  replicated twin — the runtime counterpart of the ``static_cost``
  stage's fixture-derived ``modeled_zero1_hbm_drop_pct``.  Gated by
  ``tools/bench_compare.py`` (higher, 2%: deterministic model).
- ``reshard_restore_ms`` — wall time of the resize-on-resume path: a
  shard-parallel checkpoint saved by a 4-way fleet restored into a
  2-way trainer (manifest verify + shard reassembly + re-shard +
  device placement).  Gated lower with absolute slack (1-core host).
- ``elastic_resize_bitwise_ok`` — that restore reproduced the full
  optimizer state byte-exactly.
- ``supervisor_failover_steps_lost`` — a REAL failover: the elastic
  supervisor runs ``tools/train_elastic.py`` with a chaos SIGKILL of 1
  of 2 ranks mid-run, auto-shrinks and resumes; the number is the
  shrink decision's audited ``steps_lost`` (0 at checkpoint-every-step
  cadence).  Gated lower_abs with zero slack — losing steps at this
  cadence is a policy regression.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time


def _zero1_trainer(k_devices, zero=1):
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.analysis.shard_fixtures import ZERO1_GEOMETRY as g
    from mxnet_tpu.parallel import DataParallelTrainer, make_mesh
    net = gluon.nn.HybridSequential()
    for h in g["hidden"]:
        net.add(gluon.nn.Dense(h, activation="relu"))
    net.add(gluon.nn.Dense(g["classes"]))
    net.initialize(mx.init.Xavier())
    mesh = make_mesh((k_devices,), ("data",),
                     jax.devices()[:k_devices])
    return DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": g["lr"], "momentum": g["momentum"]},
        mesh=mesh, zero=zero)


def _modeled_drop_pct():
    """The runtime-tape ZeRO-1 HBM story at the pinned geometry."""
    from mxnet_tpu.analysis.cost import analyze_fn
    from mxnet_tpu.analysis.shard_fixtures import ZERO1_GEOMETRY as g

    k = 8
    data_shape = (g["batch"] * k, g["in_dim"])
    label_shape = (g["batch"] * k,)
    tz = _zero1_trainer(1, zero=1)
    rep, findings, _ = tz.zero_report(
        data_shape=data_shape, label_shape=label_shape,
        label_dtype="int32", declared_axis_size=k)
    errors = [f for f in findings]
    tw = _zero1_trainer(1, zero=0)
    args = tw._trace_args(data_shape, label_shape, axis_size=k)
    twin = analyze_fn(
        tw._build_replica_step(), *args, axis_env=[("data", k)],
        donate_argnums=(0, 1), host_argnums=(3, 4))
    drop = twin.peak_hbm_bytes - rep.peak_hbm_bytes
    return {
        "zero1_modeled_hbm_drop_pct": round(
            100.0 * drop / twin.peak_hbm_bytes, 2),
        "zero1_runtime_peak_hbm_bytes": int(rep.peak_hbm_bytes),
        "zero1_twin_peak_hbm_bytes": int(twin.peak_hbm_bytes),
        "zero1_runtime_findings": len(errors),
    }


def _reshard_stage():
    """Save at fleet size 4, restore (re-shard) at size 2, timed."""
    import numpy as np

    import mxnet_tpu as mx

    d = tempfile.mkdtemp(prefix="mxtpu_elastic_bench_")
    try:
        t4 = _zero1_trainer(4)
        rng = np.random.RandomState(0)
        for _ in range(3):
            t4.step(mx.nd.array(rng.rand(64, 16).astype(np.float32)),
                    mx.nd.array(rng.randint(0, 10, 64)
                                .astype(np.int64)))
        t4.flush()
        t4.save_checkpoint(d, epoch=0, nbatch=2)
        plan = t4._zero_plan
        ref = [np.asarray(v)[:plan.total].copy()
               for v in t4._zero_leaves()]
        t2 = _zero1_trainer(2)
        t0 = time.perf_counter()
        t2.restore_checkpoint(d)
        restore_ms = 1e3 * (time.perf_counter() - t0)
        got = [np.asarray(v)[:t2._zero_plan.total]
               for v in t2._zero_leaves()]
        ok = all(a.tobytes() == b.tobytes() for a, b in zip(ref, got))
        return {"reshard_restore_ms": round(restore_ms, 2),
                "elastic_resize_bitwise_ok": bool(ok)}
    finally:
        shutil.rmtree(d, ignore_errors=True)


def _failover_stage():
    """Real supervisor failover through tools/train_elastic.py: SIGKILL
    1 of 2 ranks at step 3, shrink + resume, report the audited
    steps_lost.  Skipped (None) outside a repo checkout."""
    from mxnet_tpu.resilience.supervisor import read_audit

    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    repo = os.path.dirname(pkg)
    driver = os.path.join(repo, "tools", "train_elastic.py")
    if not os.path.isfile(driver):
        return {}
    d = tempfile.mkdtemp(prefix="mxtpu_elastic_failover_")
    try:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        env.pop("MXTPU_CHAOS", None)
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        # rank 1 (position 1 of 2) dies at step 3: at = (3-1)*2 + 1 + 1
        out = subprocess.run(
            [sys.executable, driver, "--supervise", "--workdir", d,
             "--ranks", "0,1", "--steps", "6", "--batch", "16",
             "--checkpoint-every", "1", "--chaos", "train.step:6:kill"],
            capture_output=True, text=True, timeout=240, env=env,
            cwd=repo)
        if out.returncode != 0:
            raise RuntimeError("failover run rc=%d: %s" % (
                out.returncode, (out.stderr or out.stdout)[-300:]))
        shrink = [rec for rec in read_audit(os.path.join(d, "audit"))
                  if rec["decision"]["action"] == "shrink"]
        if not shrink:
            raise RuntimeError("no shrink decision in the audit trail")
        dec = shrink[0]["decision"]
        return {
            "supervisor_failover_steps_lost": int(dec["steps_lost"]),
            "supervisor_failover_dead_rank": dec["dead_rank"],
        }
    finally:
        shutil.rmtree(d, ignore_errors=True)


def main():
    # the reshard stage needs a 4-way virtual mesh; pin it BEFORE any
    # jax import (all jax imports here are function-local)
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4").strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    rec = {}
    rec.update(_modeled_drop_pct())
    rec.update(_reshard_stage())
    rec.update(_failover_stage())
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
