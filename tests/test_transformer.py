"""The 2-3D-mesh transformer tier (docs/transformer.md): MeshPlan,
tensor/sequence-parallel numerics vs the replicated baseline, the
zero=1 composition, the tp_transformer_train_step budget gate + its
TP_ROW_PSUM mutation seam, chaos probes inside the mesh step, and the
bench/bench_compare wiring."""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.parallel import DataParallelTrainer, MeshPlan
from mxnet_tpu.transformer import (TransformerLM, TransformerLMConfig,
                                   layers as tlayers)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# tiny pinned geometry: every collective class present, traces in
# seconds on the CI host
CFG = dict(vocab_size=32, d_model=16, n_heads=4, n_layers=1, d_ff=32,
           seq_len=16)
STEPS = 3
TOL = 2e-5


def _batch(batch=4, seed=1):
    rng = np.random.RandomState(seed)
    x = rng.randint(0, CFG["vocab_size"],
                    size=(batch, CFG["seq_len"])).astype(np.int32)
    y = np.roll(x, -1, axis=1).astype(np.int32)
    return x, y


def _train(plan, zero=0, attention="ring", steps=STEPS, batch=4,
           cfg_extra=None):
    mx.random.seed(0)
    kw = dict(CFG, attention=attention, **(cfg_extra or {}))
    trainer = DataParallelTrainer(
        TransformerLM(TransformerLMConfig(**kw)), None, "sgd",
        {"learning_rate": 0.1, "momentum": 0.9}, mesh_plan=plan,
        zero=zero)
    x, y = _batch(batch)
    losses = []
    for _ in range(steps):
        loss = trainer.step(NDArray(jnp.asarray(x)),
                            NDArray(jnp.asarray(y)))
        losses.append(float(loss.asnumpy()))
    return trainer, losses


def _params_of(trainer):
    return {n: np.asarray(trainer._mesh_params[n])
            for n in trainer._mesh_param_names}


@pytest.fixture(scope="module")
def baseline():
    trainer, losses = _train(MeshPlan(data=1))
    return losses, _params_of(trainer)


# -- MeshPlan ---------------------------------------------------------------
def test_mesh_plan_collapse_and_resolve():
    plan = MeshPlan(data=2, model=2, sequence=2)
    assert plan.axis_names() == ("data", "model", "sequence")
    assert plan.axis_sizes() == {"data": 2, "model": 2, "sequence": 2}
    assert plan.batch_axes() == ("data", "sequence")
    # size-1 axes collapse out of the mesh, the specs and the env
    p2 = MeshPlan(data=4, model=1, sequence=2)
    assert p2.axis_names() == ("data", "sequence")
    assert ("model", 2) not in p2.axis_env()
    assert tuple(p2.batch_spec()) == ("data", "sequence")
    p3 = MeshPlan(data=1, model=1, sequence=1)
    assert p3.axis_names() == ("data",)
    assert p3.batch_axes() == ()
    # deferred data axis resolves against the pool
    p4 = MeshPlan(model=2, sequence=2).resolve(8)
    assert p4.data == 2 and p4.total == 8
    with pytest.raises(ValueError):
        MeshPlan(model=3).resolve(8)
    with pytest.raises(ValueError):
        MeshPlan(data=0)


def test_mesh_plan_coerce_spellings():
    assert MeshPlan.coerce({"data": 2, "model": 2}) == \
        MeshPlan(data=2, model=2)
    assert MeshPlan.coerce((2, 2, 2)) == MeshPlan(2, 2, 2)
    assert MeshPlan.coerce(None) is None
    with pytest.raises(ValueError):
        MeshPlan.coerce({"bogus": 2})
    with pytest.raises(ValueError):
        MeshPlan.coerce("2x2x2")


def test_trainer_mesh_tier_validation():
    blk = TransformerLM(TransformerLMConfig(**CFG))
    with pytest.raises(ValueError, match="mesh_program"):
        DataParallelTrainer(object(), None, "sgd",
                            mesh_plan=MeshPlan(model=2))
    with pytest.raises(ValueError, match="not both"):
        DataParallelTrainer(blk, None, "sgd",
                            mesh=mx.parallel.data_parallel_mesh(),
                            mesh_plan=MeshPlan(model=2))
    with pytest.raises(ValueError, match="param_spec_fn"):
        DataParallelTrainer(blk, None, "sgd",
                            mesh_plan=MeshPlan(model=2),
                            param_spec_fn=lambda n, s: None)
    # bad batch geometry fails with a named error at first step
    trainer = DataParallelTrainer(blk, None, "sgd",
                                  mesh_plan=MeshPlan(data=8))
    x = np.zeros((4, CFG["seq_len"]), np.int32)
    with pytest.raises(ValueError, match="divide by the data axis"):
        trainer.step(NDArray(jnp.asarray(x)), NDArray(jnp.asarray(x)))
    # config that does not factor over the model axis fails at build
    with pytest.raises(ValueError, match="n_heads"):
        DataParallelTrainer(
            TransformerLM(TransformerLMConfig(**dict(CFG, n_heads=3))),
            None, "sgd", mesh_plan=MeshPlan(model=2)
        ).mesh_report(data_shape=(4, CFG["seq_len"]))


# -- numerics vs the replicated baseline ------------------------------------
@pytest.mark.parametrize("plan_kw", [
    {"data": 2},
    {"model": 2},
    {"sequence": 4},                       # causal boundary: 4 chunks
    {"data": 2, "model": 2, "sequence": 2},
])
def test_mesh_matches_replicated_baseline(baseline, plan_kw):
    """TP=K / sequence-parallel / full 2x2x2 steps match the replicated
    single-axis run to float tolerance — params AND losses, over
    multiple steps (incl. the causal-mask boundary between ring
    chunks: sequence=4 puts 3 boundaries inside the window)."""
    base_losses, base_params = baseline
    trainer, losses = _train(MeshPlan(**plan_kw))
    np.testing.assert_allclose(losses, base_losses, rtol=0, atol=TOL)
    params = _params_of(trainer)
    for name, ref in base_params.items():
        np.testing.assert_allclose(
            params[name], ref, rtol=0, atol=5e-6,
            err_msg="param %r diverged under %r" % (name, plan_kw))


def test_ulysses_and_auto_attention(baseline):
    base_losses, base_params = baseline
    trainer, losses = _train(MeshPlan(sequence=2), attention="ulysses")
    assert trainer._mesh_program.attention_mode == "ulysses"
    np.testing.assert_allclose(losses, base_losses, rtol=0, atol=TOL)
    # auto picks ulysses when local heads divide, ring otherwise
    blk = TransformerLM(TransformerLMConfig(**dict(CFG,
                                                   attention="auto")))
    assert blk.mesh_program(
        MeshPlan(sequence=2)).attention_mode == "ulysses"
    assert blk.mesh_program(
        MeshPlan(model=2, sequence=4)).attention_mode == "ring"
    with pytest.raises(ValueError, match="ulysses"):
        TransformerLM(TransformerLMConfig(
            **dict(CFG, attention="ulysses"))).mesh_program(
            MeshPlan(model=2, sequence=4))


def test_zero1_model_composition_matches(baseline):
    """zero=1 (optimizer state sharded over data, per model rank)
    composes with tensor parallelism — same numerics as the replicated
    baseline."""
    base_losses, base_params = baseline
    trainer, losses = _train(MeshPlan(data=2, model=2), zero=1)
    np.testing.assert_allclose(losses, base_losses, rtol=0, atol=TOL)
    params = _params_of(trainer)
    for name, ref in base_params.items():
        np.testing.assert_allclose(params[name], ref, rtol=0,
                                   atol=5e-6)
    # the flat state leaves are physically sharded over model x data
    leaf = trainer._mesh_state_leaves[0]
    assert len(leaf.sharding.device_set) == 4


# -- static proofs ----------------------------------------------------------
def test_mesh_report_clean_and_priced_per_axis():
    trainer = DataParallelTrainer(
        TransformerLM(TransformerLMConfig(**CFG)), None, "sgd",
        {"learning_rate": 0.1, "momentum": 0.9},
        mesh_plan=MeshPlan(data=2, model=2, sequence=2))
    report, findings, shard = trainer.mesh_report(
        data_shape=(8, CFG["seq_len"]))
    assert findings == []
    per_axis = shard.collective_bytes_per_axis
    assert per_axis["model"] > 0 and per_axis["sequence"] > 0 \
        and per_axis["data"] > 0
    assert shard.extras["tp_modeled_model_axis_bytes"] == \
        per_axis["model"]
    assert shard.extras["attention_mode"] == "ring"
    assert report.transfer_d2h_bytes == 4
    # shard_report/cost_report/lint route to the mesh tier
    assert trainer.shard_report(
        data_shape=(8, CFG["seq_len"])).collective_bytes == \
        shard.collective_bytes
    assert trainer.lint(data_shape=(8, CFG["seq_len"])) == []
    assert trainer.cost_report(
        data_shape=(8, CFG["seq_len"])).flops == report.flops


def test_budget_model_clean_and_runtime_parity():
    from mxnet_tpu.analysis.budget_models import build_model
    report, findings, shard = build_model("tp_transformer_train_step")
    assert findings == []
    assert shard.extras["tp_modeled_model_axis_bytes"] == \
        shard.extras["runtime_model_axis_bytes"]
    assert shard.extras["tp_modeled_sequence_axis_bytes"] == \
        shard.extras["runtime_sequence_axis_bytes"]
    rep_u, f_u, shard_u = build_model("ulysses_attention")
    assert f_u == []
    assert shard_u.extras["seq2head_reshards"] == 4
    assert shard_u.extras["head2seq_reshards"] == 4
    assert shard_u.extras["ulysses_modeled_collective_bytes"] == \
        shard_u.extras["ulysses_formula_bytes"]


@pytest.mark.analysis
def test_tp_row_psum_seam_fails_budget_gate_rc2(tmp_path):
    """Headline mutation kill: deleting the row-parallel output psum
    (transformer/layers.py TP_ROW_PSUM) fails the STATIC_BUDGETS gate
    rc=2 with the pending-partial-sum DST001 named per parameter."""
    script = tmp_path / "mutate.py"
    script.write_text(
        "import os, sys\n"
        "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
        "from mxnet_tpu.transformer import layers\n"
        "layers.TP_ROW_PSUM = False\n"
        "from mxnet_tpu.analysis.__main__ import main\n"
        "sys.exit(main(['--cost', '--budget', %r]))\n"
        % os.path.join(REPO, "STATIC_BUDGETS.json"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, str(script)],
                          capture_output=True, text=True, cwd=REPO,
                          env=env, timeout=600)
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "DST001" in proc.stdout
    assert "PENDING PARTIAL-SUM" in proc.stdout
    assert "tp_transformer_train_step" in proc.stdout


# -- chaos / checkpoint / observability -------------------------------------
def test_chaos_fires_inside_mesh_step():
    from mxnet_tpu.resilience import chaos
    chaos.install(chaos.ChaosSchedule(
        [chaos.Fault("trainer.step", 2, "raise")]))
    try:
        trainer = DataParallelTrainer(
            TransformerLM(TransformerLMConfig(**CFG)), None, "sgd",
            {"learning_rate": 0.1},
            mesh_plan=MeshPlan(data=2, model=2, sequence=2))
        x, y = _batch()
        trainer.step(NDArray(jnp.asarray(x)), NDArray(jnp.asarray(y)))
        with pytest.raises(chaos.ChaosError):
            trainer.step(NDArray(jnp.asarray(x)),
                         NDArray(jnp.asarray(y)))
    finally:
        chaos.uninstall()


def test_checkpoint_roundtrip_mesh_tier(tmp_path):
    """Save mid-training, restore into a FRESH mesh trainer, continue:
    params bitwise-equal to the uninterrupted run."""
    trainer, _ = _train(MeshPlan(data=2, model=2), steps=2)
    path = trainer.save_checkpoint(str(tmp_path), epoch=0, nbatch=1)
    assert os.path.exists(path)
    x, y = _batch()
    trainer.step(NDArray(jnp.asarray(x)), NDArray(jnp.asarray(y)))
    want = _params_of(trainer)

    mx.random.seed(123)   # restore must bring the RNG stream back
    fresh = DataParallelTrainer(
        TransformerLM(TransformerLMConfig(**CFG)), None, "sgd",
        {"learning_rate": 0.1, "momentum": 0.9},
        mesh_plan=MeshPlan(data=2, model=2))
    cursor = fresh.restore_checkpoint(str(tmp_path))
    assert cursor["step"] == 2
    fresh.step(NDArray(jnp.asarray(x)), NDArray(jnp.asarray(y)))
    got = _params_of(fresh)
    for name in want:
        np.testing.assert_array_equal(got[name], want[name])


def test_mesh_step_bills_dispatch_and_no_collective_phase():
    """The mesh step is one device program: its host time is
    ``dispatch``'s, nothing lands in ``collective_or_ps`` and no context
    is tagged there, so the doctor names no collective knob for it."""
    from mxnet_tpu import telemetry
    telemetry.reset_attribution()
    telemetry.enable()
    try:
        _train(MeshPlan(data=2, model=2), steps=3)
        snapshot = telemetry.attribution().snapshot()
    finally:
        telemetry.disable()
        telemetry.reset_attribution()
    assert snapshot["steps"] == 2        # a window closes at the next step
    assert snapshot["phases_s"]["dispatch"] > 0.0
    assert snapshot["phases_s"]["collective_or_ps"] == 0.0
    assert "collective_or_ps" not in snapshot["context"]


# -- example + bench wiring -------------------------------------------------
def test_example_trains_end_to_end():
    """The acceptance headline: the long-context example TRAINS on the
    8-device host mesh at data=2 x model=2 x sequence=2 — loss drops."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "train_transformer_lm",
        os.path.join(REPO, "examples", "long_context",
                     "train_transformer_lm.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    import argparse
    ns = argparse.Namespace(
        steps=14, batch=4, seq_len=32, vocab=32, d_model=32, heads=4,
        layers=1, d_ff=64, lr=0.5, data=2, model=2, sequence=2,
        zero=0, attention="ring", seed=0, log_every=100, chaos="",
        report=True)
    stats = mod.train(ns, logger=lambda *a: None)
    assert stats["final_loss"] < stats["head_loss"]
    assert stats["plan"] == {"data": 2, "model": 2, "sequence": 2,
                             "pipeline": 1,
                             "axes": ["data", "model", "sequence"]}
    assert stats["collective_bytes_per_axis"]["model"] > 0
    assert stats["tokens_per_sec"] > 0


def test_example_train_step_chaos_probe():
    """The elastic tier's train.step probe fires inside the example's
    mesh training loop (the supervisor failover story covers this
    tier)."""
    import argparse
    import importlib.util
    from mxnet_tpu.resilience import chaos
    spec = importlib.util.spec_from_file_location(
        "train_transformer_lm_chaos",
        os.path.join(REPO, "examples", "long_context",
                     "train_transformer_lm.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    ns = argparse.Namespace(
        steps=6, batch=4, seq_len=32, vocab=32, d_model=16, heads=2,
        layers=1, d_ff=32, lr=0.5, data=2, model=1, sequence=2,
        zero=0, attention="ring", seed=0, log_every=100,
        chaos="train.step:3:raise", report=False)
    try:
        with pytest.raises(chaos.ChaosError, match="train.step"):
            mod.train(ns, logger=lambda *a: None)
    finally:
        chaos.uninstall()
        os.environ.pop("MXTPU_CHAOS", None)


def test_bench_compare_gates_transformer_keys(tmp_path):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_bench_compare_tp",
        os.path.join(REPO, "tools", "bench_compare.py"))
    bc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bc)
    GATES, compare = bc.GATES, bc.compare
    assert GATES["tp_modeled_model_axis_bytes"][0] == "lower_rel"
    assert GATES["seqpar_tokens_per_sec_host"][0] == "higher"
    assert GATES["tp_numerics_ok"] == ("higher", 0.0)
    import json
    rounds = []
    for n, ok in ((6, 1.0), (7, 0.0)):
        p = tmp_path / ("BENCH_r%02d.json" % n)
        p.write_text(json.dumps({
            "n": n, "cmd": "bench", "rc": 0,
            "parsed": {"tp_numerics_ok": ok,
                       "tp_modeled_model_axis_bytes": 165376,
                       "seqpar_tokens_per_sec_host": 1000.0}}))
        rounds.append(str(p))
    report = compare(rounds)
    assert "tp_numerics_ok" in report["regressions"]
    assert "tp_modeled_model_axis_bytes" not in report["regressions"]


@pytest.mark.slow
def test_transformer_bench_module():
    """The full host bench subprocess: emits the three gated keys and
    exits 0 (numerics ok, budget clean)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop("MXTPU_CHAOS", None)
    out = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.transformer.bench"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert out.returncode == 0, out.stdout + out.stderr
    import json
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["tp_numerics_ok"] == 1.0
    assert rec["tp_modeled_model_axis_bytes"] > 0
    assert rec["seqpar_tokens_per_sec_host"] > 0
