"""``HybridLM``'s latent-attention mixer, sparse-expert feed-forward and
prediction module (``transformer/mla.py``, ``transformer/moe.py``,
``transformer/hybrid.py``) against the benchmark's plain reference
(``benchmark/families/mla_moe.py``, which imports nothing of ``mxnet_tpu``),
on the CPU at small sizes with seeded random weights."""
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
from mxnet_tpu.transformer import HybridLM, HybridLMConfig, mla, moe
from mxnet_tpu.transformer import hybrid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
CELL_CONFIG = os.path.join(BENCH, "configs", "JoyAI-LLM-Flash.json")
# a configuration file of the deepseek_v3 family at a size for the CPU:
# three layers (one dense), 2 of 8 experts held, 2 a token, the module
SMALL = {
    "model_type": "joyai_llm_flash", "hidden_size": 32,
    "intermediate_size": 48, "moe_intermediate_size": 16,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 16,
    "kv_lora_rank": 8, "qk_nope_head_dim": 8, "qk_rope_head_dim": 4,
    "v_head_dim": 8, "rope_theta": 32000000, "rope_interleave": True,
    "rope_scaling": None, "rms_norm_eps": 1e-6, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "n_routed_experts": 2,
    "expert_shard": {"index": 1, "of": 4}, "num_experts_per_tok": 2,
    "n_shared_experts": 1, "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "moe_layer_freq": 1, "hidden_act": "silu", "attention_bias": False,
    "num_nextn_predict_layers": 1, "mtp_loss_weight": 0.3,
    "tie_word_embeddings": False, "vocab_size": 80, "seq_len": 32,
    "batch_per_chip": 2, "dtype": "float32",
    "optimizer": {"name": "sgd", "learning_rate": 0.05, "momentum": 0.9,
                  "wd": 1e-4}}


@pytest.fixture(scope="module")
def ref():
    """The plain reference, loaded by path as ``run.py`` loads it."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "benchmark_families_mla_moe",
        os.path.join(BENCH, "families", "mla_moe.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _from_file(config, **sizes):
    """The program's sizes from a configuration *file*, as the family's
    ``program_keys`` hands them over: the file counts the experts held under
    ``n_routed_experts``; ``from_hf``'s key is the published one, the
    router's width, and the share is an argument."""
    shard = config.get("expert_shard") or {"index": 0, "of": 1}
    return HybridLMConfig.from_hf(
        dict(config, n_routed_experts=config["n_routed_experts"]
             * shard["of"]),
        expert_shard=(shard["index"], shard["of"]), **sizes)


def _program(config, **sizes):
    sizes.setdefault("seq_len", config["seq_len"])
    sizes.setdefault("attention_block", 8)
    cfg = _from_file(config, **sizes)
    return cfg, HybridLM(cfg).mesh_program(MeshPlan(data=1))


def _layer_leaves(ref, config, seed, prefix="l1_", dtype=jnp.float32):
    weights = ref.make_weights(config, {}, seed)
    return {k[len(prefix):]: v.astype(dtype) for k, v in weights.items()
            if k.startswith(prefix)}


def _stream(seed, config, dtype=jnp.float32):
    return jax.random.normal(
        jax.random.PRNGKey(seed),
        (config["batch_per_chip"], config["seq_len"],
         config["hidden_size"])).astype(dtype)


def _close(a, b, tol):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-30), \
        np.abs(a - b).max() / np.abs(b).max()


# -- latent attention ---------------------------------------------------------
def test_rotary_turns_neighbouring_pairs_by_the_position():
    """Pair ``(2j, 2j+1)`` of position ``p`` turns by ``p theta^(-2j/r)``:
    as complex numbers, a multiplication; position 0 stands still, norms are
    kept, and a score depends on the distance of its two positions only."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 3, 8))
    theta = 32e6
    out = np.asarray(mla.rope_interleaved(x, theta))
    z = np.asarray(x)[..., 0::2] + 1j * np.asarray(x)[..., 1::2]
    angle = np.arange(16)[:, None] * theta ** (-np.arange(0, 8, 2) / 8)
    turned = z * np.exp(1j * angle)[None, :, None, :]
    np.testing.assert_allclose(out[..., 0::2], turned.real, atol=1e-5)
    np.testing.assert_allclose(out[..., 1::2], turned.imag, atol=1e-5)
    np.testing.assert_allclose(out[:, 0], np.asarray(x)[:, 0], atol=1e-6)
    q = jnp.broadcast_to(x[:, :1], x.shape)          # one vector everywhere
    q = np.asarray(mla.rope_interleaved(q, 100.0))
    np.testing.assert_allclose((q[:, 3] * q[:, 7]).sum(-1),
                               (q[:, 9] * q[:, 13]).sum(-1), rtol=1e-4)


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 4e-2)])
def test_latent_attention_is_the_references(ref, dtype, tol):
    """Low-rank paths, interleaved rotary pairs, the one rotary key shared
    by all heads, 12 query-key columns against 8 value columns, four blocks
    of query rows."""
    cfg, program = _program(SMALL)
    lp = _layer_leaves(ref, SMALL, 11, dtype=jnp.dtype(dtype))
    x = _stream(1, SMALL, jnp.dtype(dtype))
    got = program._latent_attention(lp, x)
    assert got.shape == x.shape and got.dtype == x.dtype
    f32 = {k: v.astype(jnp.float32) for k, v in lp.items()}
    with jax.default_matmul_precision("highest"):
        want = ref.attention(SMALL, f32, x.astype(jnp.float32),
                             ref.HOLD["float32"])
    _close(got, want, tol)


def test_latent_attention_is_causal_at_block_borders(ref):
    """A token changed at position 8, 15 or 16 (blocks of 8 rows) moves no
    row before it and every row from it on."""
    cfg, program = _program(SMALL)
    lp = _layer_leaves(ref, SMALL, 12)
    x = _stream(2, SMALL)
    base = np.asarray(program._latent_attention(lp, x))
    for p in (8, 15, 16):
        moved = np.asarray(program._latent_attention(
            lp, x.at[:, p].add(1.0)))
        assert np.array_equal(moved[:, :p], base[:, :p])
        assert (np.abs(moved[:, p:] - base[:, p:]).max(axis=-1) > 0).all()


# -- the router ---------------------------------------------------------------
def test_router_chooses_by_score_plus_bias_and_weighs_by_score(ref):
    cfg, _ = _program(SMALL)
    lp = _layer_leaves(ref, SMALL, 13)
    tokens = _stream(3, SMALL).reshape(-1, SMALL["hidden_size"])
    s = jax.nn.sigmoid(tokens @ lp["router"].T)
    chosen, w = moe.router_choice(tokens, lp["router"], lp["router_bias"], cfg)
    want = np.argsort(-np.asarray(s + lp["router_bias"]), axis=-1)[:, :2]
    assert np.array_equal(np.sort(chosen, -1), np.sort(want, -1))
    picked = np.take_along_axis(np.asarray(s), np.asarray(chosen), -1)
    np.testing.assert_allclose(
        w, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(w.sum(-1), 2.5, rtol=1e-5)
    # a bias that changes the choice leaves the chosen experts' scores be
    plain, _ = moe.router_choice(tokens, lp["router"],
                                 jnp.zeros_like(lp["router_bias"]), cfg)
    far = jnp.zeros_like(lp["router_bias"]).at[5].set(10.0)
    forced, w_forced = moe.router_choice(tokens, lp["router"], far, cfg)
    assert (np.asarray(forced) == 5).any(-1).all()
    assert not (np.asarray(plain) == 5).any(-1).all()
    np.testing.assert_allclose(
        w_forced, 2.5 * np.take_along_axis(np.asarray(s), np.asarray(forced),
                                           -1)
        / np.take_along_axis(np.asarray(s), np.asarray(forced), -1).sum(
            -1, keepdims=True), rtol=1e-5)
    ref_chosen, ref_w = ref.router(SMALL, lp, tokens, ref.HOLD["float32"])
    assert np.array_equal(chosen, ref_chosen)
    np.testing.assert_allclose(w, ref_w, rtol=1e-5)


# -- the share ties to the model ----------------------------------------------
def test_the_shards_parts_add_up_to_the_uncut_layer(ref):
    """8 experts over 4 shards, 2 a token: the routed parts the four shards
    compute, with the shared expert counted once, are the uncut reference's
    whole layer (all 8 experts held by one)."""
    uncut = dict(SMALL, n_routed_experts=8, expert_shard={"index": 0, "of": 1})
    whole = _layer_leaves(ref, uncut, 14)
    x = _stream(4, SMALL)
    tokens = x.reshape(-1, x.shape[-1])
    with jax.default_matmul_precision("highest"):
        want = ref.experts(uncut, whole, x, ref.HOLD["float32"])
        shared = ref._gated(tokens, whole["shared_in"], whole["shared_out"],
                            ref.HOLD["float32"]).reshape(x.shape)
    total = shared
    for index in range(4):
        part = dict(SMALL, n_routed_experts=2,
                    expert_shard={"index": index, "of": 4})
        cfg, _ = _program(part)
        assert (cfg.n_routed_experts, cfg.experts_held) == (8, 2)
        lp = dict(whole, moe_in=whole["moe_in"][2 * index:2 * index + 2],
                  moe_out=whole["moe_out"][2 * index:2 * index + 2])
        total = total + (moe.sparse_experts(lp, x, cfg) - shared)
        # the reference, given the same share, gives the same part
        with jax.default_matmul_precision("highest"):
            _close(moe.sparse_experts(lp, x, cfg),
                   ref.experts(part, lp, x, ref.HOLD["float32"]), 2e-5)
    _close(total, want, 2e-5)


# -- no token is dropped ------------------------------------------------------
@pytest.mark.parametrize("bias,rows", [
    ("all_here", "more than the buffer"), ("none_here", "none"),
    ("seeded", "some")])
def test_no_token_is_dropped_whatever_the_routing(ref, bias, rows):
    """Biases that send every token's choices to the experts held (more rows
    than the buffer has: the overflow branch), biases that send none, and the
    seed's: values and gradients are the reference's."""
    cfg, _ = _program(SMALL)                 # holds experts 2 and 3 of 8
    lp = _layer_leaves(ref, SMALL, 15)
    if bias != "seeded":
        here = (jnp.arange(8) == 2) | (jnp.arange(8) == 3)
        lp["router_bias"] = jnp.where(here == (bias == "all_here"), 5.0, -5.0)
    x = _stream(5, SMALL)
    tokens = x.shape[0] * x.shape[1]
    routed = int(moe.held_loads(lp, x, cfg).sum())
    buffer = moe.buffer_rows(cfg, tokens)
    assert buffer == 3 * moe.expected_rows(cfg, tokens) == 96
    assert {"all_here": routed == 2 * tokens > buffer,
            "none_here": routed == 0,
            "seeded": 0 < routed <= buffer}[bias], (routed, rows)
    probe = jax.random.normal(jax.random.PRNGKey(6), x.shape)

    def ours(lp, x):
        return jnp.sum(moe.sparse_experts(lp, x, cfg) * probe)

    def theirs(lp, x):
        return jnp.sum(ref.experts(SMALL, lp, x, ref.HOLD["float32"]) * probe)

    with jax.default_matmul_precision("highest"):
        _close(moe.sparse_experts(lp, x, cfg),
               ref.experts(SMALL, lp, x, ref.HOLD["float32"]), 2e-5)
        got = jax.jit(jax.grad(ours, argnums=(0, 1)))(lp, x)
        want = jax.grad(theirs, argnums=(0, 1))(lp, x)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        if float(jnp.abs(w).max()) == 0.0:
            assert float(jnp.abs(g).max()) == 0.0
        else:
            _close(g, w, 5e-5)
    assert float(jnp.abs(got[0]["router_bias"]).max()) == 0.0


def test_the_grouped_products_are_handed_the_rows_routed(ref, monkeypatch):
    """Both products' group sizes are each held expert's load: their work
    follows the rows routed here, not the buffer's static size."""
    cfg, _ = _program(SMALL)
    lp = _layer_leaves(ref, SMALL, 15)
    x = _stream(5, SMALL)
    loads = np.asarray(moe.held_loads(lp, x, cfg))
    assert 0 < loads.sum() < moe.buffer_rows(cfg, x.shape[0] * x.shape[1])
    handed = []
    grouped = jax.lax.ragged_dot

    def spy(rows, weights, sizes, **kw):
        handed.append((rows.shape[0], np.asarray(sizes)))
        return grouped(rows, weights, sizes, **kw)

    monkeypatch.setattr(moe.lax, "ragged_dot", spy)
    tokens = x.reshape(-1, x.shape[-1])
    chosen, w = moe.router_choice(tokens, lp["router"], lp["router_bias"],
                                  cfg)
    local = moe._held(chosen, cfg)
    moe._routed(lp, tokens, local, moe._loads(local, cfg.experts_held), w,
                rows=moe.buffer_rows(cfg, 64))
    assert [n for n, _ in handed] == [moe.buffer_rows(cfg, 64)] * 2
    for _, sizes in handed:
        np.testing.assert_array_equal(sizes, loads)
    assert moe.BUFFER_FACTOR == 3


# -- the prediction module and the whole loss ---------------------------------
def _batch(config, seed):
    ids = jax.random.randint(
        jax.random.PRNGKey(seed),
        (config["batch_per_chip"], config["seq_len"] + 1), 0,
        config["vocab_size"], jnp.int32)
    return ids[:, :-1], ids[:, 1:]


def _loss(program, weights, x, y):
    return program.loss_replica(
        tuple(weights[n] for n in program.param_names), x, y, None)


def test_the_module_reads_the_next_token_and_is_asked_for_the_one_after(ref):
    """Both losses against the reference's; ``lambda`` weighs the module's;
    the module's loss does not see the label of a row's last position twice
    removed: it changes with ``y[:, 1:]`` and the embedding of ``y``, and
    the main loss alone does not change with ``lambda``."""
    cfg, program = _program(SMALL)
    weights = ref.make_weights(SMALL, {}, 21)
    x, y = _batch(SMALL, 22)
    with jax.default_matmul_precision("highest"):
        want = float(ref.loss_fn(SMALL, weights, x, y, ref.HOLD["float32"]))
        main = float(ref.loss_fn(dict(SMALL, num_nextn_predict_layers=0),
                                 {k: v for k, v in weights.items()
                                  if not k.startswith("mtp_")}, x, y,
                                 ref.HOLD["float32"]))
        got = float(_loss(program, weights, x, y))
        assert got == pytest.approx(want, rel=2e-6)
        for weight in (0.0, 1.0):
            _, other = _program(dict(SMALL, mtp_loss_weight=weight))
            assert float(_loss(other, weights, x, y)) == pytest.approx(
                main + weight * (want - main) / 0.3, rel=5e-6)
        # the module's own target: the last label of a row is never asked
        # of it (the main loss is asked it), the others are
        def module_loss(y_targets):
            cut = dict(SMALL, mtp_loss_weight=1.0)
            _, one = _program(cut)
            return float(_loss(one, weights, x, y_targets)) - main
        swapped = y.at[:, 5].set((y[:, 5] + 1) % SMALL["vocab_size"])
        assert module_loss(swapped) != pytest.approx(module_loss(y), rel=1e-7)


def test_the_step_through_the_trainer_is_the_references(ref):
    """Three steps through ``DataParallelTrainer``'s mesh tier in float32:
    losses, the first gradient's and the update's norms by leaf against the
    reference, tightly; the bias's gradient is nought; and the float8
    control reads far off."""
    size = {}
    weights = ref.make_weights(SMALL, size, 31)
    cfg, program = _program(SMALL)
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
            assert first[0][name] == pytest.approx(want, rel=2e-4), name
    for name, want in reference["update_norms"].items():
        assert last[1][name] == pytest.approx(want, rel=2e-4), name
    control = ref.reference_readings(SMALL, size, 31, batches, variant="fp8")
    gaps = [abs(control["grad_norms"][k] - v) / v
            for k, v in reference["grad_norms"].items() if v]
    assert max(gaps) > 0.05


def test_bfloat16_step_is_within_its_limits_of_the_reference(ref):
    """The same three steps with bfloat16 compute copies: the losses within
    2e-3, the median leaf's first gradient within 2%, every leaf's within
    25% (widths of 32 read further from float32 than the cell's own)."""
    size = {}
    weights = ref.make_weights(SMALL, size, 32)
    cfg, _ = _program(SMALL)
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
    np.testing.assert_allclose(losses, reference["losses"], rtol=2e-3)
    gaps = sorted(abs(float(first[k]) - v) / v
                  for k, v in reference["grad_norms"].items() if v)
    assert gaps[len(gaps) // 2] < 0.02 and gaps[-1] < 0.25


# -- the configuration file ---------------------------------------------------
@pytest.fixture(scope="module")
def cell():
    with open(CELL_CONFIG) as f:
        return json.load(f)


def test_the_cell_has_680_million_parameters_and_the_references_leaves(
        ref, cell):
    """Counted from shapes alone at the cell's size: ISSUE 35's 680.5M
    within 0.1%; the reference's leaves are the program's, name for name
    and shape for shape."""
    keys, sizes = ref.program_keys(cell)
    assert keys["n_routed_experts"] == 256
    cfg = HybridLMConfig.from_hf(keys, **sizes)
    assert cfg.describe() == _from_file(
        cell, seq_len=cell["seq_len"],
        attention_block=cell["attention_block"]).describe()
    program = HybridLM(cfg).mesh_program(MeshPlan(data=1))
    spec = ref.leaves(cell, {})
    assert program.param_names == [n for n, _, _ in spec]
    assert [program.global_shape(n) for n in program.param_names] == \
        [s for _, _, s in spec]
    count = sum(math.prod(s) for _, _, s in spec)
    assert abs(count - 680.5e6) < 0.001 * 680.5e6
    assert cfg.layers == (("latent_attention", "gated_mlp"),) + (
        ("latent_attention", "sparse_experts"),) * 4
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.expert_shard,
            cfg.experts_per_token) == (256, 16, (0, 16), 8)
    assert (cfg.mtp_modules, cfg.mtp_weight, cfg.tie_embeddings) == (
        1, 0.3, False)
    assert cfg.describe()["experts_held"] == 16


@pytest.mark.parametrize("key,value", [
    ("scoring_func", "softmax"),
    ("rope_scaling", {"type": "yarn", "factor": 40}), ("moe_layer_freq", 2),
    ("num_nextn_predict_layers", 2), ("topk_method", "greedy"),
    ("rope_interleave", False),
    ("hidden_act", "gelu"), ("attention_bias", True),
    ("model_type", "mixtral")])
def test_from_hf_refuses_what_it_does_not_implement(cell, key, value):
    with pytest.raises(ValueError, match=key if key != "model_type"
                       else "mixtral"):
        HybridLMConfig.from_hf(dict(cell, **{key: value}), seq_len=64)


@pytest.mark.parametrize("keys,reads", [
    ({"n_group": 8, "topk_group": 4}, {"n_group": 8, "topk_group": 4}),
    ({"n_group": 4, "topk_group": 1}, {"n_group": 4, "topk_group": 1}),
    ({"q_lora_rank": None}, {"q_lora_rank": None})])
def test_from_hf_reads_what_pr_38_implemented(cell, keys, reads):
    """Group-limited choice and a null ``q_lora_rank`` were refused by name
    until ``moe.router_choice`` had the group step and ``mla.py`` the query
    path of one product (PR 38); the published JoyAI row has neither."""
    published = dict(cell, **cell["published"])
    cfg = HybridLMConfig.from_hf(dict(published, **keys), seq_len=64)
    assert {k: getattr(cfg, k) for k in reads} == reads
    kinds = [k for k, _ in hybrid._layer_leaves(cfg, "latent_attention")]
    assert ("wq" in kinds) == (cfg.q_lora_rank is None)
    assert ("wq_a" in kinds) == (cfg.q_lora_rank is not None)


def test_from_hf_keeps_the_published_meaning_of_n_routed_experts(cell):
    """A published ``config.json`` holds the router's width under
    ``n_routed_experts`` and no share: all are held, or the share the
    caller names; a share that does not divide the width is refused."""
    published = dict(cell, **cell["published"])
    del published["expert_shard"]
    whole = HybridLMConfig.from_hf(published, seq_len=64)
    assert (whole.n_routed_experts, whole.experts_held, whole.expert_shard,
            len(whole.layer_types), whole.vocab_size) == (
                256, 256, (0, 1), 40, 129280)
    part = HybridLMConfig.from_hf(published, seq_len=64, expert_shard=(3, 16))
    assert (part.n_routed_experts, part.experts_held) == (256, 16)
    with pytest.raises(ValueError, match="expert_shard"):
        HybridLMConfig.from_hf(published, seq_len=64, expert_shard=(0, 7))


def test_from_hf_still_refuses_granites_experts():
    with open(os.path.join(BENCH, "configs",
                           "granite-4.0-h-micro.json")) as f:
        granite = json.load(f)
    with pytest.raises(ValueError, match="num_local_experts"):
        HybridLMConfig.from_hf(dict(granite, num_local_experts=64))
    assert HybridLMConfig.from_hf(granite).ffn_types == ("gated_mlp",) * 10


# -- the memory reckoning and the counters ------------------------------------
def test_keeps_products_reckons_the_new_kinds(cell):
    """At the cell's size the products (4.5 GB) do not fit beside 9.5 GB of
    state and a layer's live set, so the layers are re-run whole; a device
    twice as large, or a quarter of the tokens, keeps them."""
    cfg = _from_file(cell, seq_len=8192, attention_block=512)
    bf16 = jnp.bfloat16
    tokens = 2 * 8192
    per_layer = tokens * (sum(mla.product_widths(cfg)) + 2 * 768) \
        + moe.buffer_rows(cfg, tokens) * 2 * 768
    assert sum(mla.product_widths(cfg)) == 1536 + 6144 + 576 + 8192 + 2048
    assert hybrid.kept_product_bytes(cfg, 2, 8192, bf16) == 2 * (
        5 * per_layer + tokens * (sum(mla.product_widths(cfg)) + 2 * 7168))
    assert moe.buffer_rows(cfg, tokens) == 24576
    assert moe.worst_rows(cfg, tokens) == 8 * tokens
    live = hybrid._layer_live_bytes(cfg, "latent_attention", 2, 8192, bf16,
                                    "sparse_experts")
    dense = hybrid._layer_live_bytes(cfg, "latent_attention", 2, 8192, bf16)
    scores = 4 * 4 * 32 * 512 * 8192
    assert live > scores and dense > scores
    rows = moe.buffer_rows(cfg, tokens)
    # the shared expert's product and gate, the float32 sum, and what the
    # buffer holds, in place of the dense feed-forward's product and gate
    assert live - dense == 2 * (
        tokens * (3 * 768 + 2 * 2048) + rows * (5 * 768 + 4 * 2048)
        - tokens * 3 * 7168)
    assert not hybrid.keeps_products(cfg, 680.5e6, 2, 8192, bf16, 16.9e9)
    assert hybrid.keeps_products(cfg, 680.5e6, 2, 8192, bf16, 33.8e9)
    assert hybrid.keeps_products(cfg, 680.5e6, 1, 4096, bf16, 16.9e9)
    assert hybrid.keeps_products(cfg, 680.5e6, 2, 8192, bf16, None)


def test_the_trace_notes_its_counters_and_the_routing_report_reads_loads(ref):
    cfg, program = _program(SMALL)
    weights = ref.make_weights(SMALL, {}, 61)
    x, y = _batch(SMALL, 62)
    before = compiles.counters()
    jax.make_jaxpr(lambda v: program.loss_replica(v, x, y, None))(
        tuple(weights[n] for n in program.param_names))
    after = compiles.counters()
    grew = {k: after[k] - before[k] for k in
            ("latent_attention_layers", "moe_layers", "mtp_modules",
             "recomputed_layers", "ssm_layers")}
    assert grew == {"latent_attention_layers": 4, "moe_layers": 3,
                    "mtp_modules": 1, "recomputed_layers": 4, "ssm_layers": 0}
    tokens = x.size
    assert (after["experts_held"], after["router_width"]) == (2, 8)
    assert after["moe_grouped_rows"] == moe.buffer_rows(cfg, tokens) == 96
    assert after["moe_expected_rows"] == 32
    report = program.routing_report(
        tuple(weights[n] for n in program.param_names), x)
    assert [r["layer"] for r in report] == ["l1", "l2"]
    for r in report:
        assert r["rows"] == sum(r["load"]) and len(r["load"]) == 2
        assert 0 < r["rows"] <= 2 * tokens
        assert r["max_over_mean"] == pytest.approx(
            max(r["load"]) / (r["rows"] / 2))
        assert (r["buffer_rows"], r["expected_rows"]) == (96, 32.0)
