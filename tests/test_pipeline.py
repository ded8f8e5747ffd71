"""The fourth mesh axis (docs/pipeline.md): MeshPlan(pipeline=K),
1F1B numerics vs the replicated baseline across the composition matrix
(pipe alone, pipe x model, pipe x zero=1, pipe x 2x2x2, bf16), the
pp_transformer_train_step budget gate + its PP_GRAD_ACCUM mutation
seam, chaos stage-death through the supervisor resuming bitwise, and
the grad_accum satellite for the replicated/ZeRO-1 tiers."""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.parallel import DataParallelTrainer, MeshPlan
from mxnet_tpu.parallel import pipeline as pp
from mxnet_tpu.transformer import TransformerLM, TransformerLMConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# tiny pinned geometry; n_layers=4 so pipe=2 AND pipe=4 both factor
CFG = dict(vocab_size=32, d_model=16, n_heads=4, n_layers=4, d_ff=32,
           seq_len=16)
STEPS = 3
BATCH = 8
TOL = 2e-5


def _batch(batch=BATCH, seed=1):
    rng = np.random.RandomState(seed)
    x = rng.randint(0, CFG["vocab_size"],
                    size=(batch, CFG["seq_len"])).astype(np.int32)
    y = np.roll(x, -1, axis=1).astype(np.int32)
    return x, y


def _train(plan, zero=0, dtype=None, steps=STEPS, batch=BATCH,
           cfg_extra=None):
    mx.random.seed(0)
    kw = dict(CFG, **(cfg_extra or {}))
    trainer = DataParallelTrainer(
        TransformerLM(TransformerLMConfig(**kw)), None, "sgd",
        {"learning_rate": 0.1, "momentum": 0.9}, mesh_plan=plan,
        zero=zero, dtype=dtype)
    x, y = _batch(batch)
    losses = []
    for _ in range(steps):
        loss = trainer.step(NDArray(jnp.asarray(x)),
                            NDArray(jnp.asarray(y)))
        losses.append(float(loss.asnumpy()))
    return trainer, losses


def _params_of(trainer):
    """Params in the replicated l{i}_* naming — stacked blk_* arrays
    unstack so pipelined and replicated runs compare name-for-name."""
    out = {}
    for n in trainer._mesh_param_names:
        v = np.asarray(trainer._mesh_params[n])
        if n.startswith("blk_"):
            for i in range(v.shape[0]):
                out["l%d_%s" % (i, n[4:])] = v[i]
        else:
            out[n] = v
    return out


@pytest.fixture(scope="module")
def baseline():
    trainer, losses = _train(MeshPlan(data=1))
    return losses, _params_of(trainer)


# -- MeshPlan: the fourth axis ----------------------------------------------
def test_mesh_plan_pipeline_axis():
    plan = MeshPlan(data=2, model=2, pipeline=2)
    assert plan.axis_names() == ("data", "model", "pipe")
    assert plan.axis_sizes() == {"data": 2, "model": 2, "pipe": 2}
    # pipe is NOT a batch axis: grads never reduce over it (DST012)
    assert "pipe" not in plan.batch_axes()
    # size-1 collapses exactly like the other axes
    p1 = MeshPlan(data=2, pipeline=1)
    assert "pipe" not in p1.axis_names()
    # deferred data resolves against what model x sequence x pipe leave
    p2 = MeshPlan(model=2, pipeline=2).resolve(8)
    assert p2.data == 2 and p2.total == 8
    assert plan.describe()["pipeline"] == 2
    assert "pipeline=2" in repr(plan)


def test_mesh_plan_pipeline_spellings():
    assert MeshPlan.coerce({"pipeline": 2}) == MeshPlan(pipeline=2)
    # the axis-name alias spells the same plan
    assert MeshPlan.coerce({"pipe": 2}) == MeshPlan(pipeline=2)
    assert MeshPlan.coerce((2, 2, 1, 2)) == \
        MeshPlan(data=2, model=2, pipeline=2)
    # the historical 3-tuple still works (pipeline defaults to 1)
    assert MeshPlan.coerce((2, 2, 2)) == MeshPlan(2, 2, 2)
    with pytest.raises(ValueError):
        MeshPlan(pipeline=0)


def test_pipeline_validation():
    # n_layers must factor into K contiguous stages
    with pytest.raises(ValueError, match="n_layers"):
        TransformerLM(TransformerLMConfig(
            **dict(CFG, n_layers=3))).mesh_program(MeshPlan(pipeline=2))
    with pytest.raises(ValueError, match="microbatches"):
        TransformerLMConfig(**dict(CFG, microbatches=0))
    # local batch must divide into the microbatches
    trainer = DataParallelTrainer(
        TransformerLM(TransformerLMConfig(**dict(CFG, microbatches=3))),
        None, "sgd", mesh_plan=MeshPlan(data=1, pipeline=2))
    x, y = _batch(4)
    with pytest.raises(ValueError, match="microbatches"):
        trainer.step(NDArray(jnp.asarray(x)), NDArray(jnp.asarray(y)))


def test_schedule_formulas():
    assert pp.pipeline_ticks(2, 4) == 5
    assert pp.pipeline_ticks(4, 4) == 7
    assert pp.bubble_fraction(2, 4) == pytest.approx(0.2)
    assert pp.bubble_fraction(4, 4) == pytest.approx(3.0 / 7.0)
    # degenerate single stage: no bubble, one tick per microbatch
    assert pp.bubble_fraction(1, 8) == 0.0
    assert pp.pipeline_ticks(1, 8) == 8


# -- numerics vs the replicated baseline ------------------------------------
@pytest.mark.parametrize("plan_kw", [
    {"pipeline": 2},                                  # data defers to 4
    {"pipeline": 4},                                  # 1 layer per stage
    {"pipeline": 2, "model": 2},
    {"data": 1, "model": 2, "sequence": 2, "pipeline": 2},   # full 4D
])
def test_pipeline_matches_replicated_baseline(baseline, plan_kw):
    """The 1F1B schedule is numerically the replicated forward: params
    AND losses match to float tolerance over multiple steps, for pipe
    alone, deeper pipe, pipe x model, and the full 4D factorization on
    the 8-device cap."""
    base_losses, base_params = baseline
    trainer, losses = _train(MeshPlan(**plan_kw))
    np.testing.assert_allclose(losses, base_losses, rtol=0, atol=TOL)
    params = _params_of(trainer)
    for name, ref in base_params.items():
        np.testing.assert_allclose(
            params[name], ref, rtol=0, atol=5e-6,
            err_msg="param %r diverged under %r" % (name, plan_kw))


def test_pipe_zero1_composition_matches(baseline):
    """The acceptance headline: pipe=2 x model=2 x zero=1 (optimizer
    state sharded over data, per (pipe, model) rank) matches the
    replicated trainer to <= 2e-5."""
    base_losses, base_params = baseline
    trainer, losses = _train(MeshPlan(data=2, model=2, pipeline=2),
                             zero=1)
    np.testing.assert_allclose(losses, base_losses, rtol=0, atol=TOL)
    params = _params_of(trainer)
    for name, ref in base_params.items():
        np.testing.assert_allclose(params[name], ref, rtol=0,
                                   atol=5e-6, err_msg=name)
    # the flat state leaves are physically sharded over the whole mesh
    leaf = trainer._mesh_state_leaves[0]
    assert len(leaf.sharding.device_set) == 8


def test_pipeline_bf16_matches_bf16_replicated():
    """bf16 composes: the pipelined bf16 run tracks the REPLICATED bf16
    run (same reduced precision, different schedule) within bf16
    resolution — microbatch reassociation is the only difference."""
    _, base_losses = _train(MeshPlan(data=1), dtype="bf16")
    trainer, losses = _train(MeshPlan(data=2, pipeline=2),
                             dtype="bf16")
    np.testing.assert_allclose(losses, base_losses, rtol=0, atol=5e-2)
    assert all(np.isfinite(losses))


def test_microbatches_knob(baseline):
    """cfg.microbatches > K deepens the schedule (more, smaller
    microbatches -> smaller bubble) without changing the numerics."""
    base_losses, base_params = baseline
    trainer, losses = _train(MeshPlan(data=1, pipeline=2),
                             cfg_extra={"microbatches": 4})
    assert trainer._mesh_program.n_micro == 4
    desc = trainer._mesh_program.describe()["pipeline"]
    assert desc == {"stages": 2, "microbatches": 4}
    np.testing.assert_allclose(losses, base_losses, rtol=0, atol=TOL)
    params = _params_of(trainer)
    for name, ref in base_params.items():
        np.testing.assert_allclose(params[name], ref, rtol=0,
                                   atol=5e-6, err_msg=name)


# -- checkpoint / supervisor ------------------------------------------------
def test_checkpoint_roundtrip_pipeline(tmp_path):
    """Save mid-training, restore into a FRESH pipelined trainer,
    continue: params bitwise-equal to the uninterrupted run."""
    trainer, _ = _train(MeshPlan(data=2, pipeline=2), steps=2)
    path = trainer.save_checkpoint(str(tmp_path), epoch=0, nbatch=1)
    assert os.path.exists(path)
    x, y = _batch()
    trainer.step(NDArray(jnp.asarray(x)), NDArray(jnp.asarray(y)))
    want = _params_of(trainer)

    mx.random.seed(123)   # restore must bring the RNG stream back
    fresh = DataParallelTrainer(
        TransformerLM(TransformerLMConfig(**CFG)), None, "sgd",
        {"learning_rate": 0.1, "momentum": 0.9},
        mesh_plan=MeshPlan(data=2, pipeline=2))
    cursor = fresh.restore_checkpoint(str(tmp_path))
    assert cursor["step"] == 2
    fresh.step(NDArray(jnp.asarray(x)), NDArray(jnp.asarray(y)))
    got = _params_of(fresh)
    for name in want:
        np.testing.assert_array_equal(got[name], want[name])


_DRIVER_SRC = """\
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, %(repo)r)
workdir, steps, out = sys.argv[1], int(sys.argv[2]), sys.argv[3]
import numpy as np
import jax.numpy as jnp
import mxnet_tpu as mx
from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.parallel import DataParallelTrainer, MeshPlan
from mxnet_tpu.resilience import chaos
from mxnet_tpu.transformer import TransformerLM, TransformerLMConfig
chaos.install_from_env()
mx.random.seed(0)
cfg = TransformerLMConfig(**%(cfg)r)
trainer = DataParallelTrainer(
    TransformerLM(cfg), None, "sgd",
    {"learning_rate": 0.1, "momentum": 0.9},
    mesh_plan=MeshPlan(data=2, model=2, pipeline=2))
start = 0
try:
    start = int(trainer.restore_checkpoint(workdir)["step"])
except Exception:
    pass
for step in range(start, steps):
    # the batch for step s is a pure function of s: any resume point
    # sees the same bytes (the train_elastic.py determinism rule)
    rng = np.random.RandomState(1000 + step)
    x = rng.randint(0, cfg.vocab_size,
                    size=(8, cfg.seq_len)).astype(np.int32)
    y = np.roll(x, -1, axis=1).astype(np.int32)
    trainer.step(NDArray(jnp.asarray(x)), NDArray(jnp.asarray(y)))
    trainer.save_checkpoint(workdir, epoch=0, nbatch=step)
names = sorted(trainer._mesh_param_names)
blob = b"".join(np.asarray(trainer._mesh_params[n]).tobytes()
                for n in names)
with open(out, "wb") as f:
    f.write(blob)
sys.exit(0)
"""


def test_stage_death_supervisor_resumes_bitwise(tmp_path):
    """Chaos SIGKILLs the pipelined job inside trainer.step (a stage
    host dying mid-schedule); the supervisor audits the death, respawns
    WITHOUT re-arming the fault, the job resumes from its checkpoint,
    and the final params are bitwise-equal to an uninterrupted run."""
    from mxnet_tpu.resilience import supervisor as sup

    driver = tmp_path / "pp_driver.py"
    driver.write_text(_DRIVER_SRC % {"repo": REPO, "cfg": CFG})
    env_base = dict(os.environ,
                    PYTHONPATH=REPO + os.pathsep
                    + os.environ.get("PYTHONPATH", ""))
    env_base.pop("MXTPU_CHAOS", None)
    steps = 4

    def _run(workdir, out, chaos_env=None, supervise=False):
        def launch(ranks, resume, extra_env):
            env = dict(env_base, **(extra_env or {}))
            return subprocess.Popen(
                [sys.executable, str(driver), workdir, str(steps), out],
                env=env, cwd=REPO, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE)
        if supervise:
            supv = sup.ElasticSupervisor(workdir, launch, [0],
                                         chaos_env=chaos_env)
            return supv.run()
        proc = launch([0], False, {})
        _, err = proc.communicate(timeout=280)
        assert proc.returncode == 0, err[-2000:]
        return None

    run_a = str(tmp_path / "run")
    out_a = str(tmp_path / "a.bin")
    os.makedirs(run_a)
    decision = _run(run_a, out_a, supervise=True,
                    chaos_env={"MXTPU_CHAOS": "trainer.step:3:kill"})
    assert decision["action"] == "complete"
    trail = sup.read_audit(os.path.join(run_a, "audit"))
    actions = [r["decision"]["action"] for r in trail]
    assert actions == ["start", "restart", "complete"], actions
    # the kill really fired: the first launch died without the blob
    assert trail[1]["evidence"]["exit_code"] != 0

    run_b = str(tmp_path / "ref")
    out_b = str(tmp_path / "b.bin")
    os.makedirs(run_b)
    _run(run_b, out_b)
    with open(out_a, "rb") as f:
        blob_a = f.read()
    with open(out_b, "rb") as f:
        blob_b = f.read()
    assert blob_a and blob_a == blob_b


# -- static proofs ----------------------------------------------------------
def test_mesh_report_pipeline_clean_and_priced():
    trainer = DataParallelTrainer(
        TransformerLM(TransformerLMConfig(**CFG)), None, "sgd",
        {"learning_rate": 0.1, "momentum": 0.9},
        mesh_plan=MeshPlan(data=2, model=2, pipeline=2))
    report, findings, shard = trainer.mesh_report(
        data_shape=(8, CFG["seq_len"]))
    assert findings == []
    per_axis = shard.collective_bytes_per_axis
    assert per_axis["pipe"] > 0 and per_axis["model"] > 0
    x = shard.extras
    assert x["pp_microbatches"] == 2            # default M = K
    assert x["pp_ticks"] == 3
    assert x["pp_modeled_bubble_frac"] == pytest.approx(1.0 / 3.0)
    # per-hop payload: one microbatch's activations
    b_local, t_local = 8 // 2, CFG["seq_len"]
    assert x["pp_hop_bytes"] == \
        (b_local // 2) * t_local * CFG["d_model"] * 4
    assert x["pp_stash_bytes"] == \
        b_local * t_local * CFG["d_model"] * 4
    assert report.peak_hbm_bytes >= x["pp_stash_bytes"]


def test_budget_model_pp_clean_and_runtime_parity():
    from mxnet_tpu.analysis.budget_models import (PP_GEOMETRY,
                                                  build_model)
    report, findings, shard = build_model("pp_transformer_train_step")
    assert findings == []
    x = shard.extras
    k = PP_GEOMETRY["pipeline"]
    m = PP_GEOMETRY["microbatches"]
    assert x["pp_modeled_bubble_frac"] == \
        pytest.approx(pp.bubble_fraction(k, m))
    assert x["pp_ticks"] == pp.pipeline_ticks(k, m)
    # fixture and the REAL trainer tape agree EXACTLY
    assert x["pp_modeled_pipe_axis_bytes"] == \
        x["runtime_pipe_axis_bytes"]
    assert x["pp_modeled_model_axis_bytes"] == \
        x["runtime_model_axis_bytes"]
    assert report.peak_hbm_bytes >= x["pp_stash_bytes"]


def test_lint_pipeline_step_catches_wrong_schedule():
    """DST011 unit: a jaxpr whose pipe ppermute is NOT the full ring /
    NOT scanned M+K-1 ticks, or whose modeled peak HBM cannot hold the
    activation stash, is named."""
    from mxnet_tpu.analysis.shard_prop import lint_pipeline_step

    def good(x):
        def tick(c, _):
            c = jax.lax.ppermute(c, "pipe", [(0, 1), (1, 0)])
            return c, ()
        c, _ = jax.lax.scan(tick, x, None, length=5)     # fwd ring
        c, _ = jax.lax.scan(tick, c, None, length=5)     # bwd ring
        return c

    closed = jax.make_jaxpr(good, axis_env=[("pipe", 2)])(
        jnp.zeros((2, 4)))
    assert lint_pipeline_step(closed, {"pipe": 2}, n_micro=4) == []
    # wrong tick count: the scan runs 5 ticks but M=8 models 9
    finds = lint_pipeline_step(closed, {"pipe": 2}, n_micro=8)
    assert any(f.rule_id == "DST011" for f in finds)
    # stash does not fit the modeled peak
    finds = lint_pipeline_step(closed, {"pipe": 2}, n_micro=4,
                               stash_bytes=1 << 40,
                               peak_hbm_bytes=1024)
    assert any(f.rule_id == "DST011" and "stash" in f.message.lower()
               for f in finds)

    def partial(x):
        def tick(c, _):
            c = jax.lax.ppermute(c, "pipe", [(0, 1)])   # broken ring
            return c, ()
        c, _ = jax.lax.scan(tick, x, None, length=5)
        c, _ = jax.lax.scan(tick, c, None, length=5)
        return c

    closed_p = jax.make_jaxpr(partial, axis_env=[("pipe", 2)])(
        jnp.zeros((2, 4)))
    finds = lint_pipeline_step(closed_p, {"pipe": 2}, n_micro=4)
    assert any(f.rule_id == "DST011" for f in finds)


def test_dst012_taints_pipe_reduced_block_grads():
    """DST012 unit: a pmean over pipe flowing into a pipe-sharded
    parameter outvar is the mixed-layer-gradients bug; the legitimate
    completing psum of a pipe-REPLICATED param passes."""
    from mxnet_tpu.analysis.shard_prop import lint_pipeline_step

    def step(w_blk, w_rep, g_blk, g_rep):
        g_blk = jax.lax.pmean(g_blk, "pipe")        # WRONG: mixes layers
        g_rep = jax.lax.psum(g_rep, "pipe")         # legitimate completion
        return w_blk - g_blk, w_rep - g_rep

    z = jnp.zeros((2, 4))
    closed = jax.make_jaxpr(step, axis_env=[("pipe", 2)])(z, z, z, z)
    finds = lint_pipeline_step(
        closed, {"pipe": 2}, n_micro=4,
        param_outvars=[0, 1], param_names=["blk_w", "embed"],
        pipe_sharded=[0])
    assert any(f.rule_id == "DST012" and "blk_w" in f.message
               for f in finds)
    assert not any(f.rule_id == "DST012" and "embed" in f.message
                   for f in finds)


@pytest.mark.analysis
def test_pp_grad_accum_seam_fails_budget_gate_rc2(tmp_path):
    """Headline mutation kill: flipping parallel/pipeline.py's
    PP_GRAD_ACCUM to the broken grads-averaged-over-pipe spelling fails
    the UNMODIFIED STATIC_BUDGETS gate rc=2 with DST012 naming the
    stacked block parameters."""
    script = tmp_path / "mutate.py"
    script.write_text(
        "import os, sys\n"
        "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"
        "from mxnet_tpu.parallel import pipeline\n"
        "pipeline.PP_GRAD_ACCUM = False\n"
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
    assert "DST012" in proc.stdout
    assert "pp_transformer_train_step" in proc.stdout
    assert "blk_" in proc.stdout


# -- grad_accum (replicated + ZeRO-1 satellite) ------------------------------
def test_accumulate_grads_bitwise_left_fold():
    """The contract: the scanned accumulation's gradient is BITWISE the
    left-fold sum of independently computed per-microbatch gradients —
    same additions, same order."""
    from mxnet_tpu.parallel.functional import accumulate_grads

    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(8, 4).astype(np.float32))
    x = jnp.asarray(rng.randn(16, 8).astype(np.float32))
    y = jnp.asarray(rng.randn(16, 4).astype(np.float32))

    def loss_fn(train_vals, xb, yb):
        (wv,) = train_vals
        return (((xb @ wv) - yb) ** 2).mean(), ()

    grad_of = jax.value_and_grad(loss_fn, has_aux=True)
    n = 4
    grads_sum, loss_sum, _ = jax.jit(
        lambda tv, xb, yb: accumulate_grads(grad_of, tv, xb, yb, n)
    )((w,), x, y)

    xm = x.reshape(n, 4, 8)
    ym = y.reshape(n, 4, 4)
    jit_grad = jax.jit(grad_of)
    acc = jnp.zeros_like(w)
    for i in range(n):
        (_, _), (g,) = jit_grad((w,), xm[i], ym[i])
        acc = acc + g
    np.testing.assert_array_equal(np.asarray(grads_sum[0]),
                                  np.asarray(acc))
    assert np.isfinite(float(loss_sum))


def _mlp_trainer(zero=0, grad_accum=1, dtype=None, seed=3):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"))
    net.add(nn.Dense(4))
    net.initialize(mx.init.Xavier())
    return DataParallelTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9}, zero=zero,
        grad_accum=grad_accum, dtype=dtype)


def _mlp_run(trainer, steps=4, seed=11):
    rng = np.random.RandomState(seed)
    x = rng.randn(32, 8).astype(np.float32)
    y = (rng.rand(32) * 4).astype(np.int64) % 4
    losses = [trainer.step(mx.nd.array(x), mx.nd.array(y)).asscalar()
              for _ in range(steps)]
    params = [p.data().asnumpy()
              for p in trainer._block.collect_params().values()]
    return losses, params


@pytest.mark.parametrize("zero,n_acc", [(0, 4), (1, 2)])
def test_grad_accum_matches_full_batch(zero, n_acc):
    """grad_accum=N runs the same global batch as N microbatches
    through one scanned left-fold before the single optimizer update —
    replicated and ZeRO-1, both within fp-reassociation noise of the
    one-shot step."""
    ref_losses, ref_params = _mlp_run(_mlp_trainer(zero=zero))
    ga_losses, ga_params = _mlp_run(_mlp_trainer(zero=zero,
                                                 grad_accum=n_acc))
    np.testing.assert_allclose(ga_losses, ref_losses, rtol=0,
                               atol=1e-6)
    for got, want in zip(ga_params, ref_params):
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)


def test_grad_accum_validation():
    with pytest.raises(ValueError, match="grad_accum"):
        _mlp_trainer(grad_accum=0)
    with pytest.raises(ValueError, match="mesh tier"):
        DataParallelTrainer(
            TransformerLM(TransformerLMConfig(**CFG)), None, "sgd",
            mesh_plan=MeshPlan(data=2), grad_accum=2)
    with pytest.raises(ValueError, match="bf16"):
        _mlp_trainer(zero=1, grad_accum=2, dtype="bf16")
    # per-replica batch must divide into the microbatches
    trainer = _mlp_trainer(grad_accum=3)
    rng = np.random.RandomState(0)
    x = rng.randn(32, 8).astype(np.float32)   # 32/8 devices = 4 local
    y = (rng.rand(32) * 4).astype(np.int64) % 4
    with pytest.raises(ValueError, match="grad_accum"):
        trainer.step(mx.nd.array(x), mx.nd.array(y))


def test_grad_accum_attribution_hint():
    from mxnet_tpu.telemetry.attribution import CONTEXT_HINTS
    assert ("dispatch", "grad_accum") in CONTEXT_HINTS


# -- bench / gate wiring ----------------------------------------------------
def test_bench_compare_gates_pipeline_keys(tmp_path):
    import importlib.util
    import json
    spec = importlib.util.spec_from_file_location(
        "_bench_compare_pp",
        os.path.join(REPO, "tools", "bench_compare.py"))
    bc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bc)
    GATES, compare = bc.GATES, bc.compare
    assert GATES["pp_modeled_bubble_frac"][0] == "lower_rel"
    assert GATES["pp_modeled_pipe_axis_bytes"][0] == "lower_rel"
    assert GATES["pp_tokens_per_sec_host"][0] == "higher"
    assert GATES["pp_numerics_ok"] == ("higher", 0.0)
    rounds = []
    for n, (ok, bub) in ((6, (1.0, 0.2)), (7, (0.0, 0.33))):
        p = tmp_path / ("BENCH_r%02d.json" % n)
        p.write_text(json.dumps({
            "n": n, "cmd": "bench", "rc": 0,
            "parsed": {"pp_numerics_ok": ok,
                       "pp_modeled_bubble_frac": bub,
                       "pp_modeled_pipe_axis_bytes": 98564,
                       "pp_tokens_per_sec_host": 1000.0}}))
        rounds.append(str(p))
    report = compare(rounds)
    assert "pp_numerics_ok" in report["regressions"]
    assert "pp_modeled_bubble_frac" in report["regressions"]
    assert "pp_modeled_pipe_axis_bytes" not in report["regressions"]


@pytest.mark.slow
def test_pipeline_bench_module():
    """The full host bench subprocess: emits the gated keys and exits 0
    (numerics ok, budget clean)."""
    import json
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.pop("MXTPU_CHAOS", None)
    out = subprocess.run(
        [sys.executable, "-m", "mxnet_tpu.transformer.pp_bench"],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert out.returncode == 0, out.stdout + out.stderr
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["pp_numerics_ok"] == 1.0
    assert rec["pp_modeled_bubble_frac"] == pytest.approx(0.2)
    assert rec["pp_tokens_per_sec_host"] > 0
