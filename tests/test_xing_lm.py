"""``HybridLM`` with several residual streams joined by manifold-constrained
hyper-connections (``transformer/mhc.py``) and YaRN-scaled latent attention
(``transformer/mla.py``): the ``xing4_0`` family of ``Xing4.0-29B-A4B``,
against the benchmark's plain reference (``benchmark/families/
mhc_mla_moe.py``, which imports nothing of ``mxnet_tpu``), on the CPU at
small sizes with seeded random weights; ``from_hf`` on the published
configuration; the cell's parameters, expert share and memory reckoning."""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.parallel import DataParallelTrainer, MeshPlan
from mxnet_tpu import telemetry
from mxnet_tpu.telemetry import compiles
from mxnet_tpu.transformer import HybridLM, HybridLMConfig, mhc, mla, moe
from mxnet_tpu.transformer import hybrid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CELL_CONFIG = os.path.join(BENCH, "configs", "Xing4.0-29B-A4B.json")
PARAMETERS = 759_346_446
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
# the published config.json, key for key
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 32, "num_nextn_predict_layers": 1, "hc_mult": 4,
    "hc_sinkhorn_iters": 20, "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": YARN, "routed_scaling_factor": 2,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 131072}
# a configuration file of the xing4_0 family at a size for the CPU: three
# layers (one dense), 4 of 8 experts held, 2 a token, four streams; YaRN
# from 16 positions, so that the 32 tokens see both kinds of turn
SMALL = dict(
    PUBLISHED, hidden_size=32, intermediate_size=48, moe_intermediate_size=16,
    num_attention_heads=4, num_key_value_heads=4, q_lora_rank=16,
    kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    num_hidden_layers=3, first_k_dense_replace=1, n_routed_experts=4,
    expert_shard={"index": 1, "of": 2}, num_experts_per_tok=2,
    num_nextn_predict_layers=0, hc_sinkhorn_iters=4, vocab_size=80,
    rope_scaling=dict(YARN, original_max_position_embeddings=16),
    seq_len=32, attention_block=8, batch_per_chip=2, dtype="float32",
    optimizer={"name": "sgd", "learning_rate": 0.05, "momentum": 0.9,
               "wd": 1e-4})


@pytest.fixture(scope="module")
def ref():
    """The plain reference, loaded by path as ``run.py`` loads it."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "benchmark_families_mhc_mla_moe",
        os.path.join(BENCH, "families", "mhc_mla_moe.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def cell():
    with open(CELL_CONFIG) as f:
        return json.load(f)


def _program(ref, config):
    keys, sizes = ref.program_keys(config)
    cfg = HybridLMConfig.from_hf(keys, **sizes)
    return cfg, HybridLM(cfg).mesh_program(MeshPlan(data=1))


def _batch(config, seed):
    ids = jax.random.randint(
        jax.random.PRNGKey(seed),
        (config["batch_per_chip"], config["seq_len"] + 1), 0,
        config["vocab_size"], jnp.int32)
    return ids[:, :-1], ids[:, 1:]


def _close(a, b, tol):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert np.isfinite(a).all()
    assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-30), \
        np.abs(a - b).max() / np.abs(b).max()


# -- YaRN ---------------------------------------------------------------------
def test_yarn_turns_are_the_hand_computed_table(ref, cell):
    """r = 64, theta 10,000, s = 64, L0 4,096, beta 32 and 1: lo 10 and hi
    23; the first ten pairs keep theta^(-2j/64), the last nine turn 64 times
    slower, the thirteen between along the ramp; cos and sin times 1; the
    scores' scale 192^-1/2 x 1.41589^2 = 0.1446796.  The program's turns are
    the reference's, computed apart."""
    cfg = HybridLMConfig.from_hf(dict(PUBLISHED, num_nextn_predict_layers=0),
                                 seq_len=64)
    turns, factor = mla.yarn(cfg)
    j = np.arange(32)
    base = 10000.0 ** (-2 * j / 64)
    ramp = np.clip((j - 10) / 13, 0, 1)
    np.testing.assert_allclose(turns, base * (1 - ramp) + base / 64 * ramp,
                               rtol=1e-6)
    np.testing.assert_allclose(turns[:10], base[:10], rtol=1e-6)
    np.testing.assert_allclose(turns[23:], base[23:] / 64, rtol=1e-6)
    assert factor == 1.0
    mscale = 0.1 * math.log(64) + 1
    assert mscale == pytest.approx(1.41589, abs=1e-5)
    assert mla.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * mscale ** 2,
                                                   rel=1e-12)
    assert mla.softmax_scale(cfg) == pytest.approx(0.144683, rel=3e-5)
    size = {k: cell[k] for k in cell["rehearsal_size"]}
    plain = ref.sized(cell, size)
    np.testing.assert_array_equal(ref.yarn_turns(plain)[0], turns)
    assert ref.scores_scale(plain) == mla.softmax_scale(cfg)
    # with mscale_all_dim absent, the turned columns carry the whole factor
    half = HybridLMConfig.from_hf(dict(
        PUBLISHED, num_nextn_predict_layers=0,
        rope_scaling={k: v for k, v in YARN.items() if k != "mscale_all_dim"}),
        seq_len=64)
    assert mla.yarn(half)[1] == pytest.approx(mscale)
    assert mla.softmax_scale(half) == 192 ** -0.5


def test_yarn_turns_the_rotary_columns_only(ref):
    """Latent attention's queries and keys with and without the scaling:
    the ``nope`` columns and the values are the same, the rotary columns of
    position 0 too, and those of later positions differ."""
    cfg, program = _program(ref, SMALL)
    params = program.init_params(3)
    lp = {n[3:]: v for n, v in params.items() if n.startswith("l1_")}
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 32, 32))
    plain = HybridLMConfig(**dict(vars(cfg), rope_scaling=None))
    q, k, v = mla.queries_keys_values(lp, x, cfg)
    q0, k0, v0 = mla.queries_keys_values(lp, x, plain)
    np.testing.assert_array_equal(q[..., :8], q0[..., :8])
    np.testing.assert_array_equal(v, v0)
    np.testing.assert_allclose(q[:, 0], q0[:, 0], rtol=1e-6)
    assert not np.allclose(k[:, 20:, :, 8:], k0[:, 20:, :, 8:])


# -- the whole model against the reference ------------------------------------
def test_every_leafs_gradient_is_the_references(ref):
    """The loss and the gradient of every leaf, value for value, of
    ``loss_replica`` in float32 against the reference's ``loss_fn`` at
    matmul precision ``highest``: within 2e-4 of the leaf's largest (the
    program applies the streams' norm factor after its product with
    ``phi``, sums the mixes in another order and runs the experts as
    grouped products over a buffer of rows, the reference every expert
    held over every token)."""
    cfg, program = _program(ref, SMALL)
    weights = ref.make_weights(SMALL, {}, 21)
    x, y = _batch(SMALL, 22)
    vals = tuple(weights[n] for n in program.param_names)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(
            lambda v: program.loss_replica(v, x, y, None))(vals)
        want, want_grads = jax.value_and_grad(
            lambda p: ref.loss_fn(SMALL, p, x, y, ref.HOLD["float32"]))(
                weights)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    for name, g in zip(program.param_names, grads):
        if "router_bias" in name:
            assert float(jnp.abs(g).max()) == 0.0
            continue
        _close(g, want_grads[name], 2e-4)


def test_every_leafs_gradient_is_the_references_on_the_kernels(ref):
    """As above at a width of 128, where the connections are the Pallas
    kernel pairs of ``ops/mhc_kernels.py`` (the dense layer alone: two
    connections, 64 tokens padded to a block of 128)."""
    config = dict(SMALL, hidden_size=128, num_hidden_layers=1)
    cfg, program = _program(ref, config)
    assert mhc.kernel_tiles(cfg, 64, jnp.float32) == (128, 128)
    weights = ref.make_weights(config, {}, 23)
    x, y = _batch(config, 24)
    vals = tuple(weights[n] for n in program.param_names)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda v: program.loss_replica(v, x, y, None)))(vals)
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p: ref.loss_fn(config, p, x, y, ref.HOLD["float32"])))(
                weights)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    for name, g in zip(program.param_names, grads):
        if "router_bias" in name:
            assert float(jnp.abs(g).max()) == 0.0
            continue
        _close(g, want_grads[name], 2e-4)


def test_the_step_through_the_trainer_is_the_references(ref):
    """Three steps through ``DataParallelTrainer``'s mesh tier in float32:
    losses, the first gradient's and the update's norms by leaf against the
    reference's readings, tightly; and the float8 control reads far off."""
    size = {}
    weights = ref.make_weights(SMALL, size, 31)
    cfg, program = _program(ref, SMALL)
    assert program.param_names == [n for n, _, _ in ref.leaves(SMALL, size)]
    batches = [_batch(SMALL, 40 + i) for i in range(3)]
    reference = ref.reference_readings(SMALL, size, 31, batches)
    opt = dict(SMALL["optimizer"])
    trainer = DataParallelTrainer(
        HybridLM(cfg, params=dict(weights)), None, opt.pop("name"), opt,
        mesh_plan=MeshPlan(data=1), dtype="float32")
    norms = ref._state_norms_fn(SMALL, size)
    import seeds
    key = seeds.key(31, stream=0)

    def snapshot():
        trainer.flush()
        params, states = trainer.device_arrays()
        return [{k: float(v) for k, v in part.items()} for part in
                jax.device_get(norms(params, dict(zip(params, states)), key))]

    with jax.default_matmul_precision("highest"):
        losses = [float(trainer.step(*batches[0])._data)]
        first = snapshot()
        losses += [float(trainer.step(*b)._data) for b in batches[1:]]
        last = snapshot()
    np.testing.assert_allclose(losses, reference["losses"], rtol=5e-6)
    for name, want in reference["grad_norms"].items():
        if "router_bias" in name:
            assert want == 0.0 and first[0][name] < 1e-6 * first[0]["embed"]
        else:
            assert first[0][name] == pytest.approx(want, rel=3e-4), name
    for name, want in reference["update_norms"].items():
        assert last[1][name] == pytest.approx(want, rel=3e-4), name
    control = ref.reference_readings(SMALL, size, 31, batches, variant="fp8")
    gaps = [abs(control["grad_norms"][k] - v) / v
            for k, v in reference["grad_norms"].items() if v]
    assert max(gaps) > 0.05


def test_bfloat16_step_is_within_its_limits_of_the_reference(ref):
    """The same three steps with bfloat16 compute copies and the streams in
    bfloat16: the losses within 3e-3, the median leaf's first gradient
    within 3%, every leaf's but the connections' within 30% (widths of 32
    read further from float32 than the cell's own).  The gradients of the
    connections' leaves are sums that all but cancel (a constant
    added to a row or a column of ``G_res`` moves nothing after Sinkhorn,
    and the streams start as copies, so only their differences reach them,
    under bfloat16's rounding of the stream): down to 1e-4 of the largest
    leaf's here, and the reference's own bfloat16 spelling reads up to 0.9
    from float32 on them."""
    size = {}
    weights = ref.make_weights(SMALL, size, 32)
    cfg, _ = _program(ref, SMALL)
    batches = [_batch(SMALL, 50 + i) for i in range(3)]
    reference = ref.reference_readings(SMALL, size, 32, batches)
    opt = dict(SMALL["optimizer"])
    trainer = DataParallelTrainer(
        HybridLM(cfg, params=dict(weights)), None, opt.pop("name"), opt,
        mesh_plan=MeshPlan(data=1), dtype="bfloat16")
    losses = [float(trainer.step(*batches[0])._data)]
    trainer.flush()
    params, states = trainer.device_arrays()
    import seeds
    first = jax.device_get(ref._state_norms_fn(SMALL, size)(
        params, dict(zip(params, states)), seeds.key(32, stream=0)))[0]
    losses += [float(trainer.step(*b)._data) for b in batches[1:]]
    np.testing.assert_allclose(losses, reference["losses"], rtol=3e-3)
    gaps = {k: abs(float(first[k]) - v) / v
            for k, v in reference["grad_norms"].items() if v}
    assert sorted(gaps.values())[len(gaps) // 2] < 0.03
    connections = [k for k in gaps if "_hc_" in k]
    assert len(connections) == 3 * 2 * 3
    assert max(v for k, v in gaps.items() if k not in connections) < 0.3


# -- from_hf ------------------------------------------------------------------
def test_from_hf_builds_the_40_layer_table_of_the_published_config():
    """The published configuration key for key: its prediction module is
    refused by name; without it, 40 latent-attention layers (two dense,
    then experts), four streams, YaRN, 64 experts of which a shard of 8
    holds 8."""
    with pytest.raises(ValueError, match="only num_nextn_predict_layers 0 "
                       "is implemented with hc_mult 4, got 1"):
        HybridLMConfig.from_hf(PUBLISHED, seq_len=64)
    cfg = HybridLMConfig.from_hf(dict(PUBLISHED, num_nextn_predict_layers=0),
                                 seq_len=64)
    assert cfg.layer_types == ("latent_attention",) * 40
    assert cfg.ffn_types == ("gated_mlp",) * 2 + ("sparse_experts",) * 38
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps,
            cfg.hc_res_clamp) == (4, 20, 1e-6, (-30.0, 30.0))
    assert cfg.rope_scaling == YARN and cfg.mtp_modules == 0
    assert (cfg.vocab_size, cfg.d_model, cfg.d_ff, cfg.moe_d_ff) == (
        131072, 3584, 9216, 1024)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_dim,
            cfg.qk_rope_dim, cfg.v_head_dim, cfg.n_heads) == (
        768, 512, 128, 64, 128, 32)
    assert (cfg.n_routed_experts, cfg.experts_per_token, cfg.routed_scaling,
            cfg.n_shared_experts, cfg.experts_held) == (64, 4, 2.0, 1, 64)
    part = HybridLMConfig.from_hf(dict(PUBLISHED, num_nextn_predict_layers=0),
                                  seq_len=64, expert_shard=(0, 8))
    assert part.experts_held == 8
    described = part.describe()
    assert described["hc_mult"] == 4 and described["rope_scaling"] == YARN


@pytest.mark.parametrize("key,value", [
    ("rope_scaling", {"type": "linear", "factor": 4}),
    ("rope_scaling", {"rope_type": "dynamic", "factor": 2}),
    ("num_nextn_predict_layers", 1), ("scoring_func", "softmax"),
    ("topk_method", "greedy"), ("rope_interleave", False),
    ("hidden_act", "gelu"), ("model_type", "xing5_0")])
def test_from_hf_refuses_what_it_does_not_implement(key, value):
    keys = dict(PUBLISHED, num_nextn_predict_layers=0)
    assert HybridLMConfig.from_hf(keys, seq_len=64).hc_mult == 4
    with pytest.raises(ValueError) as refused:
        HybridLMConfig.from_hf(dict(keys, **{key: value}), seq_len=64)
    message = str(refused.value)
    if key == "model_type":
        assert "xing5_0" in message
    else:
        assert message.startswith("only %s " % key)
        assert "is implemented" in message and "got" in message


def test_one_stream_without_scaling_reads_as_before():
    """A ``deepseek_v3`` row without the new keys builds the configuration
    it built before: one stream, no scaling, the same description."""
    keys = dict(PUBLISHED, model_type="deepseek_v3",
                num_nextn_predict_layers=0)
    for key in ("hc_mult", "hc_sinkhorn_iters", "hc_eps",
                "mhc_h_res_clamp_min", "mhc_h_res_clamp_max"):
        del keys[key]
    keys["rope_scaling"] = None
    cfg = HybridLMConfig.from_hf(keys, seq_len=64)
    assert (cfg.hc_mult, cfg.rope_scaling) == (1, None)
    assert not {"hc_mult", "rope_scaling"} & set(cfg.describe())
    assert mla.yarn(cfg) is None and mla.softmax_scale(cfg) == 192 ** -0.5


# -- the cell's size ---------------------------------------------------------
def test_the_cell_has_759_million_parameters_and_the_references_leaves(
        ref, cell):
    """Counted from shapes alone at the cell's size, leaf by leaf: the dense
    layer 128,196,918 (latent attention and its two norms 28,418,304, the
    dense feed-forward 99,090,432, two hyper-connections 688,182), an expert
    layer 40,345,974 outside its experts (the router and its bias 229,440
    and the shared expert 11,010,048 among them) and 8 x 11,010,048 in
    them, embedding and head 58,720,256 each: **759,346,446**, 10.63 GB at
    14 bytes."""
    keys, sizes = ref.program_keys(cell)
    assert keys["n_routed_experts"] == 64
    cfg = HybridLMConfig.from_hf(keys, **sizes)
    program = HybridLM(cfg).mesh_program(MeshPlan(data=1))
    spec = ref.leaves(cell, {})
    assert program.param_names == [n for n, _, _ in spec]
    assert [program.global_shape(n) for n in program.param_names] == \
        [s for _, _, s in spec]

    def count(prefix, kinds=("",)):
        return sum(math.prod(s) for n, k, s in spec
                   if n.startswith(prefix) and k.startswith(kinds))

    assert count("l0_") == 128_196_918
    assert count("l0_", ("hc_",)) == 688_182
    assert count("l0_", ("mlp_",)) == 99_090_432
    assert count("l1_", ("moe_",)) == 8 * 11_010_048
    assert count("l1_") - count("l1_", ("moe_",)) == 40_345_974
    assert count("l1_", ("shared_",)) == 11_010_048
    assert count("l1_", ("router",)) == 229_440
    assert count("embed") == count("head") == 58_720_256
    assert all(count("l%d_" % i) == count("l1_") for i in range(2, 5))
    assert ref.parameters(cell, {}) == PARAMETERS
    assert PARAMETERS * hybrid.RESIDENT_BYTES_PER_PARAM == 10_630_850_244
    assert cfg.layers == (("latent_attention", "gated_mlp"),) + (
        ("latent_attention", "sparse_experts"),) * 4
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.expert_shard,
            cfg.experts_per_token, cfg.hc_mult) == (64, 8, (0, 8), 4, 4)


def test_all_8_shares_add_up_to_the_uncut_layer(ref):
    """Xing's router: 64 experts, 4 a token, scaling 2, norm_topk_prob, no
    groups, over 8 shards of 8: the routed parts the eight shares compute,
    with the shared expert counted once, are the uncut reference's whole
    layer."""
    wide = dict(SMALL, n_routed_experts=64, num_experts_per_tok=4,
                routed_scaling_factor=2, seq_len=6,
                expert_shard={"index": 0, "of": 1})
    whole = {k[3:]: v for k, v in ref.make_weights(wide, {}, 15).items()
             if k.startswith("l1_")}
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 6, 32))
    tokens = x.reshape(-1, 32)
    hold = ref.HOLD["float32"]
    with jax.default_matmul_precision("highest"):
        want = ref.experts(wide, whole, x, hold)
        shared = ref._gated(tokens, whole["shared_in"], whole["shared_out"],
                            hold).reshape(x.shape)
    total, rows = shared, 0
    for index in range(8):
        share, _ = _program(ref, dict(
            wide, n_routed_experts=8,
            expert_shard={"index": index, "of": 8}))
        assert (share.n_routed_experts, share.experts_held) == (64, 8)
        held = slice(8 * index, 8 * index + 8)
        lp = dict(whole, moe_in=whole["moe_in"][held],
                  moe_out=whole["moe_out"][held])
        total = total + (moe.sparse_experts(lp, x, share) - shared)
        rows += int(moe.held_loads(lp, x, share).sum())
    assert rows == 4 * tokens.shape[0]
    _close(total, want, 3e-5)


def test_keeps_products_reckons_the_streams(ref, cell):
    """At the cell's size 10.63 GB are resident, the products of five layers
    2.2 GB and the six streams the checkpoints keep 1.41 GB: the layers are
    re-run whole on a v5e (16.9e9 bytes); a device twice as large keeps the
    products.  A layer's live set counts the four-wide stream."""
    keys, sizes = ref.program_keys(cell)
    cfg = HybridLMConfig.from_hf(keys, **sizes)
    one = HybridLMConfig(**dict(vars(cfg), hc_mult=1))
    bf16 = jnp.bfloat16
    kept = hybrid.kept_product_bytes(cfg, 2, 4096, bf16)
    assert 2.0e9 < kept < 2.5e9
    live = hybrid._layer_live_bytes(cfg, "latent_attention", 2, 4096, bf16)
    alone = hybrid._layer_live_bytes(one, "latent_attention", 2, 4096, bf16)
    stream = 2 * 4096 * 4 * 3584
    assert live - alone == 6 * stream * 2 + 2 * stream * 4
    assert not hybrid.keeps_products(cfg, PARAMETERS, 2, 4096, bf16, 16.9e9)
    assert hybrid.keeps_products(cfg, PARAMETERS, 2, 4096, bf16, 33.8e9)
    assert hybrid.keeps_products(cfg, PARAMETERS, 2, 4096, bf16, None)
    # the single stream's reckoning would have kept them: the streams decide
    assert hybrid.keeps_products(one, PARAMETERS, 2, 4096, bf16, 16.9e9)


def test_the_trace_notes_its_counters_and_scopes(ref):
    cfg, program = _program(ref, SMALL)
    weights = ref.make_weights(SMALL, {}, 61)
    x, y = _batch(SMALL, 62)
    before = compiles.counters()
    lowered = jax.jit(lambda v: program.loss_replica(v, x, y, None)).lower(
        tuple(weights[n] for n in program.param_names))
    after = compiles.counters()
    grew = {k: after[k] - before[k] for k in
            ("hyper_connection_sublayers", "hc_kernel_sublayers",
             "yarn_rope_layers", "attention_layers", "recomputed_layers")}
    # a width of 32 does not tile: the connections are the spelling
    assert grew == {"hyper_connection_sublayers": 6, "hc_kernel_sublayers": 0,
                    "yarn_rope_layers": 3, "attention_layers": 3,
                    "recomputed_layers": 3}
    text = telemetry.render_doctor({
        "directory": "d", "ranks": {"worker0": {"compiles": grew}},
        "stragglers": [], "events": dict.fromkeys(
            ("straggler", "anomaly", "queue_growth", "fault"), ())})
    assert ("6 sublayer(s) in hyper-connections in the traced programs, as "
            "Pallas kernel pairs in 0 of them; 3 latent-attention layer(s) "
            "with YaRN positions") in text
    text = lowered.as_text(debug_info=True)
    for scope in ("hyper_connection", "hc_coeffs", "hc_read_in",
                  "hc_write_out", "mla_rope", "attention", "sparse_experts"):
        assert scope in text, scope


def test_the_routing_report_reads_the_feed_forwards_own_input(ref):
    """Where the feed-forward reads ``u``, the mix of the streams, the
    report's loads are those of ``u``: two choices a token, of which those
    of the four experts held here (of eight) are counted."""
    cfg, program = _program(ref, SMALL)
    weights = ref.make_weights(SMALL, {}, 71)
    x, _ = _batch(SMALL, 72)
    report = program.routing_report(
        [weights[n] for n in program.param_names], x)
    assert [r["layer"] for r in report] == ["l1", "l2"]
    for r in report:
        assert len(r["load"]) == 4 and 0 < r["rows"] <= 2 * x.size
