"""``HybridLM``'s delta-rule linear-attention mixer (``transformer/kda.py``),
latent attention without the low-rank query path and with the head-wise gate
(``transformer/mla.py``), and the router's group step
(``transformer/moe.py``), against the recurrence that defines the mixer and
against the benchmark's plain reference (``benchmark/families/
kda_mla_moe.py``, which imports nothing of ``mxnet_tpu``), on the CPU at
small sizes with seeded random weights."""
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
from mxnet_tpu.telemetry import compiles
from mxnet_tpu.transformer import HybridLM, HybridLMConfig, kda, moe
from mxnet_tpu.transformer import hybrid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CELL_CONFIG = os.path.join(BENCH, "configs", "Ling-3.0-flash.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PARAMETERS = 822_060_224
# a configuration file of the bailing_hybrid family at a size for the CPU:
# three layers (linear, latent, linear; the first dense), 4 of 16 experts
# held, 4 groups of which 2 are kept, 2 experts a token
SMALL = {
    "model_type": "bailing_hybrid", "hidden_size": 32,
    "intermediate_size": 48, "moe_intermediate_size": 16,
    "moe_shared_expert_intermediate_size": 16, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 8, "q_lora_rank": None,
    "kv_lora_rank": 8, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
    "rotary_dim": 4, "v_head_dim": 8, "rope_theta": 6000000,
    "rope_interleave": True, "rope_scaling": None, "rms_norm_eps": 1e-6,
    "num_hidden_layers": 3, "layer_group_size": 2,
    "first_k_dense_replace": 1, "num_experts": 4,
    "expert_shard": {"index": 1, "of": 4}, "num_experts_per_tok": 2,
    "num_shared_experts": 1, "n_group": 4, "topk_group": 2,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "score_function": "sigmoid", "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "hidden_act": "silu",
    "short_conv_kernel_size": 4, "kda_lower_bound": -5,
    "kda_safe_gate": True, "linear_silu": True, "use_qk_norm": True,
    "no_kda_lora": True, "use_kda_lora": False, "group_norm_size": 1,
    "gated_attention_proj_granularity_type": "head_wise",
    "moe_router_enable_expert_bias": True, "num_nextn_predict_layers": 0,
    "tie_word_embeddings": False, "vocab_size": 80, "seq_len": 40,
    "kda_chunk": 16, "attention_block": 8, "batch_per_chip": 2,
    "dtype": "float32",
    "optimizer": {"name": "sgd", "learning_rate": 0.05, "momentum": 0.9,
                  "wd": 1e-4}}


@pytest.fixture(scope="module")
def ref():
    """The plain reference, loaded by path as ``run.py`` loads it."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "benchmark_families_kda_mla_moe",
        os.path.join(BENCH, "families", "kda_mla_moe.py"))
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


def _layer_leaves(ref, config, seed, prefix, dtype=jnp.float32):
    weights = ref.make_weights(config, {}, seed)
    return {k[len(prefix):]: v.astype(dtype) for k, v in weights.items()
            if k.startswith(prefix)}


def _stream(seed, config, dtype=jnp.float32):
    return jax.random.normal(
        jax.random.PRNGKey(seed),
        (config["batch_per_chip"], config["seq_len"],
         config["hidden_size"])).astype(dtype)


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


def _scan_inputs(seed, t, heads=2, width=8, batch=2, g=None):
    """q, k, v, g, beta as the mixer hands them to the scan: q and k of unit
    length a head (q scaled), the gate spread over (-5, 0) or all at
    ``g``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (batch, t, heads, width)
    q = kda._l2_normed(jax.random.normal(ks[0], shape)) * width ** -0.5
    k = kda._l2_normed(jax.random.normal(ks[1], shape))
    v = jax.random.normal(ks[2], shape)
    gate = -5 * jax.nn.sigmoid(3 * jax.random.normal(ks[3], shape) - 2) \
        if g is None else jnp.full(shape, g, jnp.float32)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3]))
    return q, k, v, gate, beta


# -- the chunked delta rule is the recurrence ---------------------------------
@pytest.mark.parametrize("t,chunk", [(192, 64), (200, 64), (50, 32),
                                     (64, 16), (1100, 32), (24, 8)])
def test_chunked_is_the_recurrence_in_values_and_gradients(t, chunk):
    """Several chunks, a length that is no multiple of the chunk, more than
    one checkpointed block (1,100 > 32 x 32), chunks of one sub-block: in
    float32 the chunked form gives the token-by-token recurrence's output
    and its gradients by all five operands, to rounding."""
    args = _scan_inputs(t, t)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    want = kda.kda_recurrence(*args)
    _close(kda.kda_chunked(*args, chunk), want, 1e-5)
    by_all = tuple(range(5))
    want_grads = jax.grad(lambda *a: jnp.sum(kda.kda_recurrence(*a) * weight),
                          argnums=by_all)(*args)
    got_grads = jax.grad(
        lambda *a: jnp.sum(kda.kda_chunked(*a, chunk) * weight),
        argnums=by_all)(*args)
    for got, wanted in zip(got_grads, want_grads):
        _close(got, wanted, 2e-5)


def test_chunked_in_bfloat16_is_near_the_recurrence():
    """Operands of the products in bfloat16, the gate, its sums, the system
    and the state in float32: within 3% of the float32 recurrence in value,
    5% in every gradient."""
    args = _scan_inputs(7, 200)
    low = tuple(a.astype(jnp.bfloat16) for a in args[:3]) + args[3:]
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    got = kda.kda_chunked(*low, 64)
    assert got.dtype == jnp.bfloat16
    _close(got, kda.kda_recurrence(*args), 3e-2)
    want_grads = jax.grad(lambda *a: jnp.sum(kda.kda_recurrence(*a) * weight),
                          argnums=(0, 1, 2, 3, 4))(*args)
    got_grads = jax.grad(
        lambda *a: jnp.sum(kda.kda_chunked(*a, 64).astype(jnp.float32)
                           * weight), argnums=(0, 1, 2, 3, 4))(*low)
    for got, wanted in zip(got_grads, want_grads):
        _close(got, wanted, 5e-2)


@pytest.mark.parametrize("g", [-5.0, -4.99, 0.0])
def test_a_gate_at_its_bound_stays_finite_and_exact(g):
    """Every g = -5 for two whole chunks: a chunk decays by e^-320, and the
    exponents relative to the middle of each sub-block of 16 stay within
    e^+-40, in the factors and in their cotangents: value and gradients are
    the recurrence's to rounding (with the reference where a sub-block
    starts, the gradient by k is off by 5e-4 there).  No decay at all
    (g = 0) is the plain delta rule."""
    args = _scan_inputs(128, 128, g=g)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    _close(kda.kda_chunked(*args, 64), kda.kda_recurrence(*args), 1e-5)
    want = jax.grad(lambda *a: jnp.sum(kda.kda_recurrence(*a) * weight),
                    argnums=(0, 1, 2, 3, 4))(*args)
    got = jax.grad(lambda *a: jnp.sum(kda.kda_chunked(*a, 64) * weight),
                   argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(got, want):
        _close(a, b, 1e-4)


@pytest.mark.parametrize("noise,beta,g", [(0.3, 0.5, -0.01),
                                          (0.1, 0.9, -0.001),
                                          (0.01, 0.99, -0.0001)])
def test_keys_that_point_the_same_way_stay_exact(noise, beta, g):
    """A chunk whose keys all but coincide, with ``beta`` near 1 and hardly
    any decay: what a few steps of training make of a layer's keys.  The
    system's entries are then near 1 and the powers of ``A`` reach 1e18:
    the nilpotent product ``(I - A)(I + A^2)...`` read 4e3, 3e18 and 6e21
    off here (and trained to NaN on the chip in ten steps); forward
    substitution in blocks stays at rounding, in value and gradients."""
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    shape = (2, 128, 2, 16)
    base = jax.random.normal(ks[0], (1, 1, 2, 16))
    k = kda._l2_normed(base + noise * jax.random.normal(ks[1], shape))
    q = kda._l2_normed(base + noise * jax.random.normal(ks[2], shape)) / 4
    v = jax.random.normal(ks[3], shape)
    args = (q, k, v, jnp.full(shape, g), jnp.full(shape[:3], beta))
    weight = jax.random.normal(ks[4], shape)
    _close(kda.kda_chunked(*args, 64), kda.kda_recurrence(*args), 1e-5)
    want = jax.grad(lambda *a: jnp.sum(kda.kda_recurrence(*a) * weight),
                    argnums=(0, 1, 2, 3, 4))(*args)
    got = jax.grad(lambda *a: jnp.sum(kda.kda_chunked(*a, 64) * weight),
                   argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(got, want):
        _close(a, b, 5e-4)


def test_a_bound_the_sub_blocks_cannot_hold_is_refused():
    with pytest.raises(ValueError, match="kda_lower_bound"):
        HybridLMConfig(layer_types=("linear_attention",), kda_chunk=64,
                       kda_lower_bound=-6.0)
    # eight tokens a sub-block carry it; so does a table without the mixer
    HybridLMConfig(layer_types=("linear_attention",), kda_chunk=8,
                   kda_lower_bound=-6.0)
    HybridLMConfig(layer_types=("attention",), kda_chunk=64,
                   kda_lower_bound=-6.0)


@pytest.mark.parametrize("size", [64, 48, 16, 5])
def test_the_inverse_of_a_unit_lower_system_is_exact(size):
    """Entries as the delta rule's with aligned keys, ``0.9 e^{-0.01 (i -
    j)}``: the inverse is bounded by 1 and ``(I + a) T = I`` to rounding,
    at sizes that halve evenly, that do not, and at a single block; its
    gradient is ``-T^T g T^T``."""
    i = jnp.arange(size)
    a = jnp.tril(0.9 * jnp.exp(-0.01 * (i[:, None] - i[None, :])), -1)
    a = a[None] * jax.random.uniform(jax.random.PRNGKey(3), (3, size, size),
                                     minval=0.8, maxval=1.0)
    inverse = kda._unit_lower_inverse(a)
    assert float(jnp.abs(inverse).max()) <= 1.0 + 1e-5
    np.testing.assert_allclose(
        jnp.einsum("bij,bjk->bik", jnp.eye(size) + a, inverse,
                   precision="highest"),
        np.broadcast_to(np.eye(size), (3, size, size)), atol=1e-5)
    weight = jax.random.normal(jax.random.PRNGKey(4), a.shape)
    got = jax.grad(lambda x: jnp.sum(kda._unit_lower_inverse(x) * weight))(a)
    want = jax.grad(lambda x: jnp.sum(kda._inverse(x) * weight))(a)
    np.testing.assert_allclose(jnp.tril(got, -1), jnp.tril(want, -1),
                               atol=2e-5)


def test_equal_halves_of_the_system_are_solved_as_one_batch():
    """A chunk of 64 is one forward substitution over its four diagonal
    blocks of 16 (15 row products) and two products a halving (4), not four
    substitutions: these rows were a third of the cell's step.  Each
    diagonal block of the result is that block's own inverse, bit for
    bit."""
    a = jnp.tril(jax.random.uniform(jax.random.PRNGKey(5), (3, 64, 64),
                                    minval=-0.9, maxval=0.9), -1)
    assert str(jax.make_jaxpr(kda._inverse)(a)).count("dot_general") == 19
    whole = kda._inverse(a)
    for i in range(0, 64, kda.SUB_BLOCK):
        block = slice(i, i + kda.SUB_BLOCK)
        np.testing.assert_array_equal(whole[:, block, block],
                                      kda._inverse(a[:, block, block]))


# -- the mixer whole ----------------------------------------------------------
@pytest.mark.parametrize("dtype,tol", [("float32", 3e-5), ("bfloat16", 5e-2)])
def test_the_mixer_is_the_references(ref, dtype, tol):
    """Projections, three short convolutions, the L2 norms, the bounded
    gate, the chunked scan over 40 tokens in chunks of 16, the norm over
    all columns and the head-wise gate, against the reference's recurrence
    a group of heads at a time."""
    cfg, _ = _program(ref, SMALL)
    lp = _layer_leaves(ref, SMALL, 11, "l0_", jnp.dtype(dtype))
    x = _stream(1, SMALL, jnp.dtype(dtype))
    got = kda.kda_mixer(lp, x, cfg)
    assert got.shape == x.shape and got.dtype == x.dtype
    f32 = {k: v.astype(jnp.float32) for k, v in lp.items()}
    with jax.default_matmul_precision("highest"):
        want = ref.linear_attention(SMALL, f32, x.astype(jnp.float32),
                                    ref.HOLD["float32"])
    _close(got, want, tol)


def test_the_mixer_is_causal(ref):
    """A change of the stream from token 21 on leaves the mixer's first 21
    outputs as they were: the convolutions look back only, and a chunk's
    later tokens (and the padding after the last) reach no earlier one;
    the later outputs all move."""
    cfg, _ = _program(ref, SMALL)
    lp = _layer_leaves(ref, SMALL, 12, "l2_")
    x = _stream(2, SMALL)
    moved = x.at[:, 21:].add(_stream(3, SMALL)[:, 21:])
    before, after = kda.kda_mixer(lp, x, cfg), kda.kda_mixer(lp, moved, cfg)
    # to rounding, not to the bit: a sub-block's exponents are relative to
    # the running sum in its middle, which cancels in every product
    _close(after[:, :21], before[:, :21], 2e-6)
    assert np.abs(np.asarray(before[:, 21:] - after[:, 21:])).min(
        axis=-1).max() > 0


def test_the_gate_is_bounded_and_spread_by_its_initialisation(cell, ref):
    """At the cell's widths the seed's ``A_log`` and ``dt_bias`` spread g
    over (-5, 0): a tenth of the channels decay by less than e^-0.01 a
    token and a tenth by more than e^-3."""
    key = jax.random.PRNGKey(5)
    f = jax.random.normal(key, (256, 32, 128))
    a_log = ref._draw(jax.random.fold_in(key, 1), "kda_a_log", (32,), 4)
    bias = ref._draw(jax.random.fold_in(key, 2), "kda_dt_bias", (4096,), 4)
    g = -5 * jax.nn.sigmoid(jnp.exp(a_log)[:, None]
                            * (f + bias.reshape(32, 128)))
    assert -5 < float(g.min()) and float(g.max()) < 0
    assert float(jnp.mean(g > -0.01)) > 0.1
    assert float(jnp.mean(g < -3)) > 0.1
    program = HybridLM(HybridLMConfig(layer_types=("linear_attention",))
                       ).mesh_program(MeshPlan(data=1))
    drawn = program.init_params(3)
    assert float(jnp.abs(drawn["l0_kda_a_log"]).max()) <= math.log(2.0)
    assert -8 <= float(drawn["l0_kda_dt_bias"].min())
    assert float(drawn["l0_kda_dt_bias"].max()) <= 2


# -- latent attention without the low-rank query path, with the gate ----------
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 4e-2)])
def test_gated_latent_attention_is_the_references(ref, dtype, tol):
    cfg, program = _program(ref, SMALL)
    assert cfg.q_lora_rank is None and cfg.attention_gate
    lp = _layer_leaves(ref, SMALL, 13, "l1_", jnp.dtype(dtype))
    assert "wq" in lp and "w_gate" in lp and "wq_a" not in lp
    x = _stream(4, SMALL, jnp.dtype(dtype))
    got = program._latent_attention(lp, x)
    f32 = {k: v.astype(jnp.float32) for k, v in lp.items()}
    with jax.default_matmul_precision("highest"):
        want = ref.attention(SMALL, f32, x.astype(jnp.float32),
                             ref.HOLD["float32"])
    _close(got, want, tol)
    # the gate is one scalar a head: with W_g nought every head is halved
    halved = program._latent_attention(
        dict(lp, w_gate=jnp.zeros_like(lp["w_gate"])), x)
    ungated = HybridLM(HybridLMConfig(**{
        **{k: getattr(cfg, k) for k in (
            "vocab_size", "d_model", "n_heads", "n_kv_heads", "kv_lora_rank",
            "qk_nope_dim", "qk_rope_dim", "v_head_dim", "rope_theta",
            "norm_eps", "attention_block", "seq_len")},
        "layer_types": ("latent_attention",), "q_lora_rank": None})
    ).mesh_program(MeshPlan(data=1))
    plain = ungated._latent_attention(
        {k: v for k, v in lp.items() if k != "w_gate"}, x)
    _close(halved, 0.5 * plain.astype(jnp.float32), max(tol, 1e-5))


# -- the group step -----------------------------------------------------------
def _router_cfg(**kw):
    sizes = dict(n_routed_experts=16, experts_per_token=2, n_group=4,
                 topk_group=2, expert_shard=(0, 4), routed_scaling=2.5)
    sizes.update(kw)
    return HybridLMConfig(**sizes)


def test_the_best_expert_of_a_group_that_is_not_kept_is_not_chosen():
    """Four groups of four, two kept, two a token.  Group 3 holds the single
    best expert (0.9) and nothing else; groups 0 and 1 hold two good ones
    each (0.7 + 0.6, 0.65 + 0.6): by the sum of each group's two best they
    are kept and the best expert is passed over."""
    cfg = _router_cfg()
    s = np.full((1, 16), 0.1, np.float32)
    s[0, [0, 1]] = 0.7, 0.6
    s[0, [4, 5]] = 0.65, 0.6
    s[0, 12] = 0.9
    logits = jnp.log(s / (1 - s))
    router = jnp.eye(16, dtype=jnp.float32)
    chosen, w = moe.router_choice(logits, router, jnp.zeros(16), cfg)
    assert sorted(np.asarray(chosen)[0]) == [0, 4]
    np.testing.assert_allclose(np.sort(np.asarray(w)[0]),
                               2.5 * np.array([0.65, 0.7]) / 1.35, rtol=1e-5)
    # without groups the best expert is chosen first
    plain, _ = moe.router_choice(logits, router, jnp.zeros(16),
                                 _router_cfg(n_group=1, topk_group=1))
    assert sorted(np.asarray(plain)[0]) == [0, 12]


def test_the_bias_changes_the_kept_groups_and_never_the_weights():
    """A bias on group 3's two weak experts lifts the group's score over
    group 1's: its best expert is now chosen, and weighs by its score alone
    (0.9, not 0.9 + b)."""
    cfg = _router_cfg()
    s = np.full((1, 16), 0.1, np.float32)
    s[0, [0, 1]] = 0.7, 0.6
    s[0, [4, 5]] = 0.65, 0.6
    s[0, 12] = 0.9
    logits = jnp.log(s / (1 - s))
    router = jnp.eye(16, dtype=jnp.float32)
    bias = jnp.zeros(16).at[13].set(0.4)
    chosen, w = moe.router_choice(logits, router, bias, cfg)
    assert sorted(np.asarray(chosen)[0]) == [0, 12]
    np.testing.assert_allclose(np.sort(np.asarray(w)[0]),
                               2.5 * np.array([0.7, 0.9]) / 1.6, rtol=1e-5)


def test_the_group_step_is_the_references(ref):
    cfg, _ = _program(ref, SMALL)
    lp = _layer_leaves(ref, SMALL, 14, "l1_")
    tokens = _stream(5, SMALL).reshape(-1, SMALL["hidden_size"])
    chosen, w = moe.router_choice(tokens, lp["router"], lp["router_bias"],
                                  cfg)
    ref_chosen, ref_w = ref.router(SMALL, lp, tokens, ref.HOLD["float32"])
    assert np.array_equal(np.sort(chosen, -1), np.sort(ref_chosen, -1))
    np.testing.assert_allclose(np.sort(w, -1), np.sort(ref_w, -1), rtol=1e-5)
    # every choice lies in one of the token's two kept groups
    groups = np.asarray(chosen) // 4
    assert all(len(set(row)) <= 2 for row in groups)
    ungrouped, _ = moe.router_choice(
        tokens, lp["router"], lp["router_bias"],
        _router_cfg(n_group=1, topk_group=1, d_model=32))
    assert not np.array_equal(np.sort(ungrouped, -1), np.sort(chosen, -1))


def test_all_64_shares_add_up_to_the_uncut_layer(ref):
    """64 experts in 8 groups of which 4 are kept, 8 a token, over 64
    shards: the routed parts the 64 shares compute, with the shared expert
    counted once, are the uncut reference's whole layer."""
    wide = dict(SMALL, num_experts=64, n_group=8, topk_group=4,
                num_experts_per_tok=8, seq_len=6,
                expert_shard={"index": 0, "of": 1})
    whole = _layer_leaves(ref, wide, 15, "l1_")
    x = _stream(6, wide)
    tokens = x.reshape(-1, x.shape[-1])
    hold = ref.HOLD["float32"]
    with jax.default_matmul_precision("highest"):
        want = ref.experts(wide, whole, x, hold)
        shared = ref._gated(tokens, whole["shared_in"], whole["shared_out"],
                            hold).reshape(x.shape)
    part = dict(wide, num_experts=1, expert_shard={"index": 0, "of": 64})
    cfg, _ = _program(ref, part)
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.n_group) == (64, 1, 8)

    total, rows = shared, 0
    for index in range(64):
        share, _ = _program(ref, dict(
            part, expert_shard={"index": index, "of": 64}))
        lp = dict(whole, moe_in=whole["moe_in"][index:index + 1],
                  moe_out=whole["moe_out"][index:index + 1])
        total = total + (moe.sparse_experts(lp, x, share) - shared)
        rows += int(moe.held_loads(lp, x, share).sum())
    assert rows == 8 * tokens.shape[0]
    _close(total, want, 3e-5)


# -- the whole step -----------------------------------------------------------
def test_the_step_through_the_trainer_is_the_references(ref):
    """Three steps through ``DataParallelTrainer``'s mesh tier in float32:
    losses, the first gradient's and the update's norms by leaf against the
    reference, tightly; the bias's gradient is nought; and the float8
    control reads far off."""
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
    """The same three steps with bfloat16 compute copies: the losses within
    3e-3, the median leaf's first gradient within 3%, every leaf's within
    30% (widths of 32 read further from float32 than the cell's own)."""
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
    gaps = sorted(abs(float(first[k]) - v) / v
                  for k, v in reference["grad_norms"].items() if v)
    assert gaps[len(gaps) // 2] < 0.03 and gaps[-1] < 0.3


# -- the configuration file and from_hf ---------------------------------------
def test_the_cell_has_822_million_parameters_and_the_references_leaves(
        ref, cell):
    """Counted from shapes alone at the cell's size, leaf by leaf: a KDA
    mixer 52,650,016, the latent mixer 31,965,696, an expert feed-forward
    54,395,392 (8 experts, the shared one, the router and its bias), the
    dense one 47,185,920, embedding and head 50,298,880 each:
    **822,060,224** (ISSUE 38's arithmetic gives 822,060,416: 192 more,
    which no leaf of its equations accounts for).  The reference's leaves
    are the program's, name for name and shape for shape."""
    keys, sizes = ref.program_keys(cell)
    assert keys["num_experts"] == 512
    cfg = HybridLMConfig.from_hf(keys, **sizes)
    program = HybridLM(cfg).mesh_program(MeshPlan(data=1))
    spec = ref.leaves(cell, {})
    assert program.param_names == [n for n, _, _ in spec]
    assert [program.global_shape(n) for n in program.param_names] == \
        [s for _, _, s in spec]

    def count(prefix, kinds):
        return sum(math.prod(s) for n, k, s in spec
                   if n.startswith(prefix) and k.startswith(kinds))

    assert count("l0_", ("kda_",)) == 52_650_016
    assert count("l5_", ("w", "norm_kv")) == 31_965_696
    assert count("l5_", ("router", "moe_", "shared_")) == 54_395_392
    assert count("l0_", ("mlp_",)) == 47_185_920
    assert count("embed", ("embed",)) == count("head", ("head",)) \
        == 50_298_880
    assert ref.parameters(cell, {}) == PARAMETERS
    assert PARAMETERS * hybrid.RESIDENT_BYTES_PER_PARAM == 11_508_843_136
    assert cfg.layer_types == ("linear_attention",) * 5 + (
        "latent_attention", "linear_attention")
    assert list(cfg.layer_types) == cell["layer_types"]
    assert cfg.ffn_types == ("gated_mlp",) + ("sparse_experts",) * 6
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.expert_shard,
            cfg.experts_per_token, cfg.n_group, cfg.topk_group) == (
                512, 8, (0, 64), 8, 8, 4)
    assert (cfg.n_heads, cfg.kda_head_dim, cfg.kda_conv, cfg.kda_chunk,
            cfg.kda_lower_bound) == (32, 128, 4, 64, -5.0)
    assert (cfg.mtp_modules, cfg.tie_embeddings, cfg.q_lora_rank,
            cfg.attention_gate, cfg.rope_theta) == (0, False, None, True,
                                                    6e6)
    described = cfg.describe()
    assert {"kda_head_dim", "kda_chunk", "kda_lower_bound", "n_group",
            "topk_group", "attention_gate", "q_lora_rank"} <= set(described)
    assert described["experts_held"] == 8


@pytest.fixture(scope="module")
def row():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog beside the model-configs guide")
    with open(CATALOG) as f:
        return next(r for r in map(json.loads, f)
                    if r["name"] == "Ling-3.0-flash")["config"]


def test_from_hf_builds_the_42_layer_table_of_the_catalogs_row(row):
    """The catalog row's ``config`` key for key, with the two things the
    program cannot run taken out by name first (the prediction module, the
    clamps of layers 34-41): layers 5, 11, ..., 41 latent, the other 35
    linear; two dense layers, 40 of 512 experts in 8 groups."""
    with pytest.raises(ValueError, match="only num_nextn_predict_layers 0 "
                       "is implemented, got 1"):
        HybridLMConfig.from_hf(row, seq_len=64)
    no_module = dict(row, num_nextn_predict_layers=0)
    with pytest.raises(ValueError, match="only expert_swiglu_limit_list 0"):
        HybridLMConfig.from_hf(no_module, seq_len=64)
    # the 34 layers without a clamp are read as published
    cfg = HybridLMConfig.from_hf(dict(no_module, num_hidden_layers=34),
                                 seq_len=64)
    assert len(cfg.layer_types) == 34
    unclamped = dict(no_module, expert_swiglu_limit_list=[0] * 42,
                     share_expert_swiglu_limit_list=[0] * 42)
    cfg = HybridLMConfig.from_hf(unclamped, seq_len=64, kda_chunk=64)
    latent = [i for i, m in enumerate(cfg.layer_types)
              if m == "latent_attention"]
    assert latent == [5, 11, 17, 23, 29, 35, 41]
    assert cfg.layer_types.count("linear_attention") == 35
    assert cfg.ffn_types == ("gated_mlp",) * 2 + ("sparse_experts",) * 40
    assert (cfg.vocab_size, cfg.d_model, cfg.d_ff, cfg.moe_d_ff) == (
        157184, 2560, 6144, 768)
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.expert_shard) == (
        512, 512, (0, 1))
    part = HybridLMConfig.from_hf(unclamped, seq_len=64, expert_shard=(0, 64))
    assert part.experts_held == 8


@pytest.mark.parametrize("key,value", [
    ("use_kda_lora", True), ("no_kda_lora", False), ("kda_safe_gate", False),
    ("linear_silu", False), ("use_nGPT", True), ("value_norm", True),
    ("up_proj_norm", True), ("scale_router_input", True),
    ("score_function", "softmax"), ("scoring_func", "softmax"),
    ("num_nextn_predict_layers", 1), ("q_lora_rank", 1536),
    ("use_qk_norm", False), ("group_norm_size", 32),
    ("gated_attention_proj_granularity_type", "element_wise"),
    ("rope_scaling", {"type": "yarn", "factor": 4}), ("rotary_dim", 128),
    ("topk_method", "greedy"), ("hidden_act", "gelu"), ("use_bias", True),
    ("moe_shared_expert_intermediate_size", 1536),
    ("expert_swiglu_limit_list", [0, 0, 4] + [0] * 39),
    ("share_expert_swiglu_limit_list", [5] + [0] * 41),
    ("model_type", "bailing_moe_v9")])
def test_from_hf_refuses_what_it_does_not_implement(cell, key, value):
    keys, sizes = dict(cell), {"seq_len": 64}
    keys["num_experts"] = 512
    assert HybridLMConfig.from_hf(keys, **sizes).n_group == 8
    with pytest.raises(ValueError) as refused:
        HybridLMConfig.from_hf(dict(keys, **{key: value}), **sizes)
    message = str(refused.value)
    if key == "model_type":
        assert "bailing_moe_v9" in message
    else:
        assert message.startswith("only %s " % key)
        assert "is implemented, got" in message


def test_groups_that_cannot_give_the_choices_are_refused():
    with pytest.raises(ValueError, match="groups"):
        HybridLMConfig(n_routed_experts=16, n_group=3)
    with pytest.raises(ValueError, match="groups"):
        HybridLMConfig(n_routed_experts=16, n_group=8, topk_group=1,
                       experts_per_token=4)


# -- the memory reckoning and the counters ------------------------------------
def test_keeps_products_reckons_the_new_kind(ref, cell):
    """At the cell's size 11.5 GB are resident and the products of seven
    layers are 5.4 GB: the layers are re-run whole on a v5e; a device twice
    as large keeps them.  The linear-attention layer's live set is the
    chunked scan's block, with no score matrix of the sequence's length."""
    keys, sizes = ref.program_keys(cell)
    cfg = HybridLMConfig.from_hf(keys, **sizes)
    bf16 = jnp.bfloat16
    assert kda.product_widths(cfg) == [4096] * 4 + [2560]
    kept = hybrid.kept_product_bytes(cfg, 1, 16384, bf16)
    assert 5.0e9 < kept < 6.0e9
    live = hybrid._layer_live_bytes(cfg, "linear_attention", 1, 16384, bf16,
                                    "sparse_experts")
    shorter = hybrid._layer_live_bytes(cfg, "linear_attention", 1, 8192,
                                       bf16, "sparse_experts")
    assert 1.0e9 < live < 4.0e9
    # what grows with the sequence is the tokens' intermediates; the scan's
    # block is as large at half the length
    assert live - shorter < 0.5 * live + 1
    assert not hybrid.keeps_products(cfg, PARAMETERS, 1, 16384, bf16, 16.9e9)
    assert hybrid.keeps_products(cfg, PARAMETERS, 1, 16384, bf16, 33.8e9)
    assert hybrid.keeps_products(cfg, PARAMETERS, 1, 16384, bf16, None)


def test_the_trace_notes_its_counters(ref):
    cfg, program = _program(ref, SMALL)
    weights = ref.make_weights(SMALL, {}, 61)
    x, y = _batch(SMALL, 62)
    before = compiles.counters()
    lowered = jax.jit(lambda v: program.loss_replica(v, x, y, None)).lower(
        tuple(weights[n] for n in program.param_names))
    after = compiles.counters()
    grew = {k: after[k] - before[k] for k in
            ("linear_attention_layers", "latent_attention_layers",
             "attention_layers", "moe_layers", "recomputed_layers",
             "ssm_layers", "mtp_modules")}
    assert grew == {"linear_attention_layers": 2,
                    "latent_attention_layers": 1, "attention_layers": 1,
                    "moe_layers": 2, "recomputed_layers": 3, "ssm_layers": 0,
                    "mtp_modules": 0}
    assert after["kda_chunks_per_seq"] == 3          # 40 tokens in 16s
    assert after["moe_groups_kept"] == 2
    assert (after["experts_held"], after["router_width"]) == (4, 16)
    text = lowered.as_text(debug_info=True)
    for scope in ("kda_mixer", "kda_in_proj", "kda_conv", "kda_gate",
                  "kda_scan", "kda_out_norm", "kda_out_proj", "mla_gate",
                  "moe_group_choice"):
        assert scope in text, scope
