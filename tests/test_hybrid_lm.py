"""``transformer.HybridLM`` (Mamba-2 beside grouped-query attention) and
``transformer/ssm.py``, on the CPU at small widths with seeded weights.

The plain reference is ``benchmark/families/granite_hybrid.py``, imported
where it lies (it shares no function with ``mxnet_tpu/transformer/``).
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
SEED = 2 ** 31 + 4242


def _load(path, name):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def family():
    return _load(os.path.join(BENCH, "families", "granite_hybrid.py"),
                 "granite_hybrid_reference")


@pytest.fixture(scope="module")
def correctness():
    return _load(os.path.join(BENCH, "correctness.py"), "correctness")


def _config(**sizes):
    with open(os.path.join(BENCH, "configs",
                           "granite-4.0-h-micro.json")) as f:
        config = json.load(f)
    size = dict(config["rehearsal_size"], **sizes)
    return config, size


# -- the three spellings of the state-space map ------------------------------
def _scan_inputs(t, b=2, h=3, p=4, n=5):
    ks = jax.random.split(jax.random.PRNGKey(t), 5)
    return (jax.random.normal(ks[0], (b, t, h, p)),
            jax.nn.softplus(jax.random.normal(ks[1], (b, t, h))),
            jnp.log(jnp.arange(1, h + 1.0)),
            jax.random.normal(ks[2], (b, t, n)),
            jax.random.normal(ks[3], (b, t, n)),
            jax.random.normal(ks[4], (b, t, h, p)))


@pytest.mark.parametrize("t,chunk", [(32, 8), (32, 32), (37, 8), (5, 8)])
def test_chunked_scan_is_the_recurrence_and_the_quadratic_form(t, chunk):
    """Values and gradients, for sequences that are and are not multiples
    of the chunk (and one shorter than a chunk)."""
    from mxnet_tpu.transformer import ssm
    *args, weight = _scan_inputs(t)

    def scored(fn):
        return jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a) * weight), argnums=(0, 1, 2, 3, 4))

    forms = {"recurrence": ssm.ssd_recurrence,
             "quadratic": ssm.ssd_quadratic,
             "chunked": lambda *a: ssm.ssd_chunked(*a, chunk)}
    values = {k: fn(*args) for k, fn in forms.items()}
    grads = {k: scored(fn)(*args)[1] for k, fn in forms.items()}
    for other in ("quadratic", "chunked"):
        np.testing.assert_allclose(values[other], values["recurrence"],
                                   rtol=2e-4, atol=2e-5)
        for got, want in zip(grads[other], grads["recurrence"]):
            np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_reference_recurrence_is_the_same_map(family):
    """The reference's own scan (blocks of time, recomputed) against the
    program's defining recurrence: two spellings that share no code."""
    from mxnet_tpu.transformer import ssm
    x, dt, a_log, B, C, _ = _scan_inputs(36)
    with jax.default_matmul_precision("highest"):
        want = family._recurrence(x, dt, -jnp.exp(a_log), B, C)
    np.testing.assert_allclose(ssm.ssd_recurrence(x, dt, a_log, B, C), want,
                               rtol=1e-5, atol=1e-5)
    assert family._time_block(4096) == 64 and family._time_block(36) == 6


# -- the model under the trainer against the reference -----------------------
def _trained(family, config, size, steps, dtype):
    """(program readings, feed) of ``steps`` steps of ``HybridLM`` under
    ``DataParallelTrainer(mesh_plan=...)`` at ``size``."""
    from mxnet_tpu.parallel import make_mesh
    kind = _load(os.path.join(BENCH, "traffic_kinds",
                              "device_resident_tokens.py"), "tokens_kind")
    config = dict(config, dtype=dtype)
    mesh = make_mesh((1,), ("data",), jax.devices()[:1])
    feed = kind.batches(config, size, mesh, SEED, {"distinct_batches": steps})
    program = family.build(config, size, mesh, SEED)
    losses = [program.step(*feed[0])]
    after_first = program.snapshot()
    losses += [program.step(*feed[i]) for i in range(1, steps)]
    after_last = program.snapshot()
    readings = program.readings(losses, after_first, after_last)
    program.close()
    return readings, feed


def test_three_steps_against_the_reference(family, correctness):
    """Losses, per-leaf gradient norms, update norms and momentum norms of
    three float32 steps through the trainer's mesh tier agree with the
    plain reference to rounding; the same steps in bfloat16 fail the
    tolerance that float32 passes."""
    config, size = _config()
    readings, feed = _trained(family, config, size, 3, "float32")
    assert sorted(readings["grad_norms"]) == sorted(
        n for n, _, _ in family.leaves(config, size))
    for x, y in feed:
        assert x.dtype == jnp.int32 and int(x.max()) < size["vocab_size"]
        np.testing.assert_array_equal(x[:, 1:], y[:, :-1])
    reference = family.reference_readings(config, size, SEED, feed)
    tight = correctness.compare(readings, reference)
    tolerance = 2e-4
    assert all(v[0] < tolerance for v in tight.values()), tight
    np.testing.assert_allclose(readings["losses"], reference["losses"],
                               rtol=1e-5)
    lower, _ = _trained(family, config, size, 3, "bfloat16")
    loose = correctness.compare(lower, reference)
    assert max(v[0] for v in loose.values()) > 10 * tolerance, loose


def test_vocabulary_slice_and_tied_embedding(family):
    """Ids come from the held rows, the logits are over the held rows, and
    the tied embedding's gradient is the sum of the gradient that reaches
    it through the lookup and the one through the head, each once."""
    from mxnet_tpu.parallel import MeshPlan
    from mxnet_tpu.transformer import HybridLM, HybridLMConfig
    config, size = _config(batch_per_chip=1, num_hidden_layers=2)
    cfg = family.sized(config, size)
    weights = family.make_weights(config, size, SEED)
    program = HybridLM(HybridLMConfig.from_hf(
        cfg, seq_len=size["seq_len"])).mesh_program(MeshPlan(data=1))
    assert program.global_shape("embed") == (size["vocab_size"],
                                             size["hidden_size"])
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, size["seq_len"] + 1),
                             0, size["vocab_size"] // 2)
    x, y = ids[:, :-1], ids[:, 1:]
    vals = tuple(weights[n] for n in program.param_names)
    got = jax.grad(program.loss_replica)(vals, x, y, None)[0]

    hold = family.HOLD["float32"]

    def untied(table_in, table_out):
        h = family.final_hidden(cfg, dict(weights, embed=table_in), x, hold)
        return family.head_loss(cfg, h, table_out, y, hold)

    with jax.default_matmul_precision("highest"):
        through_lookup, through_head = jax.grad(untied, argnums=(0, 1))(
            weights["embed"], weights["embed"])
    np.testing.assert_allclose(got, through_lookup + through_head,
                               rtol=2e-4, atol=1e-7)
    unseen = size["vocab_size"] - 1        # no id names the upper half
    assert not np.any(np.asarray(through_lookup[unseen]))
    assert np.any(np.asarray(through_head[unseen]))
    assert np.any(np.asarray(got[unseen]))


@pytest.mark.parametrize("block", [16, 64, 24])
def test_grouped_query_attention_against_the_reference(family, block):
    """An attention-only model, score scale 1/64, 4 query heads on 2
    key-value heads: loss and every gradient against the reference, for
    query blocks that divide the sequence and one that does not."""
    from mxnet_tpu.parallel import MeshPlan
    from mxnet_tpu.transformer import HybridLM, HybridLMConfig
    config, size = _config(batch_per_chip=2, num_hidden_layers=1)
    config = dict(config, layer_types=["attention"])
    cfg = family.sized(config, size)
    assert cfg["attention_multiplier"] == 1 / 64
    weights = family.make_weights(config, size, SEED)
    program = HybridLM(HybridLMConfig.from_hf(
        cfg, seq_len=size["seq_len"],
        attention_block=block)).mesh_program(MeshPlan(data=1))
    ids = jax.random.randint(jax.random.PRNGKey(2), (2, size["seq_len"] + 1),
                             0, size["vocab_size"])
    x, y = ids[:, :-1], ids[:, 1:]
    loss, grads = jax.value_and_grad(program.loss_replica)(
        tuple(weights[n] for n in program.param_names), x, y, None)
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(
            lambda p: family.loss_fn(cfg, p, x, y, family.HOLD["float32"])
        )(weights)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    for name, got in zip(program.param_names, grads):
        np.testing.assert_allclose(got, want[name], rtol=5e-4, atol=1e-7,
                                   err_msg=name)


def test_layer_table_refusals_and_counters():
    from mxnet_tpu.parallel import MeshPlan
    from mxnet_tpu.telemetry import compiles
    from mxnet_tpu.transformer import (HybridLM, HybridLMConfig,
                                       HybridProgram)
    with pytest.raises(ValueError, match="layer_types"):
        HybridLMConfig(layer_types=("mamba", "window"))
    with pytest.raises(ValueError, match="divides no layer"):
        HybridLM(HybridLMConfig()).mesh_program(MeshPlan(data=1, model=2))
    cfg = HybridLMConfig(layer_types=("mamba", "attention", "mamba"),
                         seq_len=20, ssm_chunk=8)
    program = HybridLM(cfg).mesh_program(MeshPlan(data=1))
    assert isinstance(program, HybridProgram)
    params = program.init_params()
    assert [params[n].shape for n in program.param_names] == [
        program.local_shape(n) for n in program.param_names]
    again = program.init_params()
    np.testing.assert_array_equal(params["l0_ssm_in"], again["l0_ssm_in"])
    before = compiles.counters()
    x = jnp.zeros((1, 20), jnp.int32)
    jax.make_jaxpr(program.loss_replica)(
        tuple(params[n] for n in program.param_names), x, x, None)
    after = compiles.counters()
    assert after["ssm_layers"] - before["ssm_layers"] == 2
    assert after["recomputed_layers"] - before["recomputed_layers"] == 3
    assert after["ssm_chunks_per_seq"] == 3


def test_data_axis_of_two_matches_one_device():
    """The mesh tier's data axis: two replicas of half the batch take the
    step one replica of the whole batch takes."""
    from mxnet_tpu.parallel import DataParallelTrainer, MeshPlan
    from mxnet_tpu.transformer import HybridLM, HybridLMConfig
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    cfg = HybridLMConfig(layer_types=("mamba", "attention"), seq_len=16)
    ids = np.random.RandomState(0).randint(0, 64, (4, 17)).astype(np.int32)
    losses = {}
    for data in (1, 2):
        trainer = DataParallelTrainer(
            HybridLM(cfg), None, "sgd",
            {"learning_rate": 0.05, "momentum": 0.9},
            mesh_plan=MeshPlan(data=data))
        losses[data] = [float(trainer.step(ids[:, :-1], ids[:, 1:]).asnumpy())
                        for _ in range(3)]
    np.testing.assert_allclose(losses[2], losses[1], rtol=1e-5)


# -- what a layer's checkpoint keeps for the backward pass -------------------
def _rehearsal_program(family, **sizes):
    """(config, program, weights by name order, x, y) at the rehearsal
    size, float32."""
    from mxnet_tpu.parallel import MeshPlan
    from mxnet_tpu.transformer import HybridLM, HybridLMConfig
    config, size = _config(**sizes)
    cfg = HybridLMConfig.from_hf(family.sized(config, size),
                                 seq_len=size["seq_len"])
    program = HybridLM(cfg).mesh_program(MeshPlan(data=1))
    weights = family.make_weights(config, size, SEED)
    ids = jax.random.randint(
        jax.random.PRNGKey(3), (size["batch_per_chip"], size["seq_len"] + 1),
        0, size["vocab_size"])
    vals = tuple(weights[n] for n in program.param_names)
    return cfg, program, vals, ids[:, :-1], ids[:, 1:]


def _with_limit(monkeypatch, limit):
    """The device reports ``limit`` bytes of memory (None: none)."""
    from mxnet_tpu.transformer import hybrid
    monkeypatch.setattr(hybrid, "_device_bytes_limit", lambda: limit)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def _count_products(jaxpr, *shapes):
    """``dot_general``s whose two operands and result have ``shapes`` in
    some order, each with its axes in some order: a product and the two
    products of its backward pass."""
    want = sorted(sorted(shape) for shape in shapes)
    return sum(
        eqn.primitive.name == "dot_general"
        and sorted(sorted(v.aval.shape)
                   for v in (*eqn.invars, *eqn.outvars)) == want
        for eqn in _equations(jaxpr))


def test_kept_products_leave_loss_and_gradients_as_they_were(
        family, monkeypatch):
    """With room the projection products are residuals, without it nothing
    is: the same loss and the same gradient of every leaf."""
    _, program, vals, x, y = _rehearsal_program(family)
    got = {}
    for name, limit in (("kept", None), ("nothing", 1)):
        _with_limit(monkeypatch, limit)
        got[name] = jax.jit(jax.value_and_grad(program.loss_replica))(
            vals, x, y, None)
    np.testing.assert_allclose(got["kept"][0], got["nothing"][0], rtol=1e-6)
    for name, kept, nothing in zip(program.param_names, got["kept"][1],
                                   got["nothing"][1]):
        scale = float(jnp.max(jnp.abs(nothing)))
        np.testing.assert_allclose(kept, nothing, rtol=1e-6,
                                   atol=1e-6 * scale, err_msg=name)


@pytest.mark.parametrize("limit,in_backward", [(None, 2), (1, 3)])
def test_which_products_the_backward_pass_runs_again(
        family, monkeypatch, limit, in_backward):
    """In the jaxpr of the gradient: ``x @ ssm_in`` and ``x @ mlp_in``
    stand once in the forward pass and twice (kept) or three times (run
    again) in the backward pass; the scan's four einsums stand as often
    either way, so a policy that kept nothing, or kept the scan, fails."""
    cfg, program, vals, x, y = _rehearsal_program(family)
    _with_limit(monkeypatch, limit)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(program.loss_replica))(
        vals, x, y, None).jaxpr
    b, t = x.shape
    d, n, h, p = cfg.d_model, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    mamba = cfg.layer_types.count("mamba")
    width = 2 * cfg.ssm_inner + 2 * n + h
    assert _count_products(jaxpr, (b, t, d), (d, width), (b, t, width)) \
        == (1 + in_backward) * mamba
    assert _count_products(jaxpr, (b, t, d), (d, 2 * cfg.d_ff),
                           (b, t, 2 * cfg.d_ff)) \
        == (1 + in_backward) * len(cfg.layer_types)
    # the scan, told by the chunked shapes of ``C B^T`` and of the masked
    # decay matrix's product with ``dt x``: forward, again, two backward
    c, L = t // cfg.ssm_chunk, cfg.ssm_chunk
    assert _count_products(jaxpr, (b, c, L, n), (b, c, L, n),
                           (b, c, L, L)) == 4 * mamba
    assert _count_products(jaxpr, (b, c, h, L, L), (b, c, L, h, p),
                           (b, c, L, h, p)) == 4 * mamba


def test_the_decision_is_a_function_of_shapes_and_the_limit():
    """The cell's shapes on a 16.9 GB chip keep 2.16 GB of products; twice
    the tokens do not fit; a device that reports no limit keeps."""
    from mxnet_tpu.transformer import HybridLMConfig
    from mxnet_tpu.transformer.hybrid import (keeps_products,
                                              kept_product_bytes)
    config, _ = _config()
    cfg = HybridLMConfig.from_hf(config, seq_len=config["seq_len"])
    n_params, bf16 = 772.2e6, jnp.bfloat16
    assert kept_product_bytes(cfg, 1, 4096, bf16) == pytest.approx(
        2.16e9, rel=0.02)
    assert keeps_products(cfg, n_params, 1, 4096, bf16, 16.9e9)
    assert not keeps_products(cfg, n_params, 2, 4096, bf16, 16.9e9)
    assert keeps_products(cfg, n_params, 2, 4096, bf16, None)
    # float32 copies of the same tokens are twice the bytes
    assert not keeps_products(cfg, n_params, 1, 4096, jnp.float32, 16.9e9)


@pytest.mark.parametrize("limit,layers", [(None, 3), (1, 0)])
def test_kept_product_counters(monkeypatch, limit, layers):
    from mxnet_tpu.parallel import MeshPlan
    from mxnet_tpu.telemetry import compiles
    from mxnet_tpu.transformer import HybridLM, HybridLMConfig
    from mxnet_tpu.transformer.hybrid import kept_product_bytes
    cfg = HybridLMConfig(layer_types=("mamba", "attention", "mamba"),
                         seq_len=20, ssm_chunk=8)
    program = HybridLM(cfg).mesh_program(MeshPlan(data=1))
    params = program.init_params()
    _with_limit(monkeypatch, limit)
    before = compiles.counters()
    x = jnp.zeros((2, 20), jnp.int32)
    jax.make_jaxpr(program.loss_replica)(
        tuple(params[n] for n in program.param_names), x, x, None)
    after = compiles.counters()
    assert after["kept_product_layers"] - before["kept_product_layers"] \
        == layers
    assert after["recomputed_layers"] - before["recomputed_layers"] == 3
    # mamba: 2 x 64 + 2 x 8 + 4 and 32; attention: 32 + 16 + 16 + 32; each
    # layer 2 x 64 for the feed-forward; 40 tokens of float32
    kept = 40 * 4 * (2 * (148 + 32) + 96 + 3 * 128)
    assert kept_product_bytes(cfg, 2, 20, jnp.float32) == kept
    assert after["kept_product_bytes"] == (kept if layers else 0)
    # the doctor's state-space line prints both counts
    from mxnet_tpu import telemetry
    traced = {k: after[k] - before[k] for k in after}
    traced["kept_product_bytes"] = after["kept_product_bytes"]
    text = telemetry.render_doctor({
        "directory": "d", "ranks": {"worker0": {"compiles": traced}},
        "stragglers": [], "events": dict.fromkeys(
            ("straggler", "anomaly", "queue_growth", "fault"), ())})
    assert ("3 layer(s) recomputed in the backward pass, %d of them with "
            "their projection products kept (%.2f GB)"
            % (layers, after["kept_product_bytes"] / 1e9)) in text
