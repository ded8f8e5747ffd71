"""``HybridLM``'s causal attention as the flash kernels of
``ops/pallas_kernels.py``: the same map as ``hybrid._attend_rows`` over the
whole sequence, in values and in all three gradients, at two widths and with
grouped key-value heads; which shapes take the kernels; what the traced
gradient holds (no array of scores, the forward kernel once a layer under the
layer's checkpoint); the kernels under a ``data`` mesh; their counter and
the doctor's line; the memory reckoning; their declared costs.

On the CPU the kernels run in the Pallas interpreter
(``pallas_kernels.resolve_interpret``); that Mosaic takes them at the cells'
widths is ``tests/test_ssd_kernel.py``'s, the one file that describes a
chip."""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.transformer import hybrid

# (e_qk, e_v, heads, kv_heads): JoyAI's widths, Granite's group of four,
# one key-value head for all
SHAPES = [(192, 128, 4, 4), (64, 64, 8, 2), (128, 128, 2, 1)]
T = 384                                         # three blocks of 128


def _inputs(t, e_qk, e_v, heads, kv_heads, dtype, b=2):
    ks = jax.random.split(jax.random.PRNGKey(t + e_qk + heads), 4)
    draw = lambda k, *shape: jax.random.normal(k, shape).astype(dtype)
    return (draw(ks[0], b, t, heads, e_qk), draw(ks[1], b, t, kv_heads, e_qk),
            draw(ks[2], b, t, kv_heads, e_v),
            jax.random.normal(ks[3], (b, t, heads, e_v)))


def _whole(q, k, v, scale):
    """``_attend_rows`` over the whole sequence: the reference."""
    b, t, heads, e = q.shape
    kv = k.shape[2]
    out = hybrid._attend_rows(q.reshape(b, t, kv, heads // kv, e), k, v,
                              scale, 0)
    return out.reshape(b, t, heads, v.shape[-1])


def _scored(fn, weight, scale):
    return jax.value_and_grad(
        lambda q, k, v: jnp.sum(fn(q, k, v, scale).astype(jnp.float32)
                                * weight), argnums=(0, 1, 2))


def _kernel(q, k, v, scale):
    return hybrid.causal_gqa_attention(q, k, v, scale, 128)


def _gap(got, wanted):
    got, wanted = (np.asarray(a, np.float32) for a in (got, wanted))
    return np.linalg.norm(got - wanted) / np.linalg.norm(wanted)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold, a
    kernel's own body (what VMEM holds) left out."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _equations(sub)


def _kernels(jaxpr):
    """The names of the jaxpr's Pallas calls, in order."""
    from mxnet_tpu.analysis.cost import kernel_name_of
    return [kernel_name_of(eqn) for eqn in _equations(jaxpr)
            if eqn.primitive.name == "pallas_call"]


# -- (a) the kernels are the reference ----------------------------------------
@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_kernel_path_is_the_rows_over_the_whole_sequence(shape):
    """float32: the value, the output and the gradients of q, k and v."""
    e_qk, e_v, heads, kv_heads = shape
    *args, weight = _inputs(T, *shape, jnp.float32)
    scale = e_qk ** -0.5
    assert pk.flash_tiles(T, heads, kv_heads, e_qk, e_v, jnp.float32) \
        == (128, 128)
    value, grads = _scored(_kernel, weight, scale)(*args)
    with jax.default_matmul_precision("highest"):
        want, want_grads = _scored(_whole, weight, scale)(*args)
        np.testing.assert_allclose(_kernel(*args, scale),
                                   _whole(*args, scale), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(value, want, rtol=2e-5)
    for got, wanted in zip(grads, want_grads):
        np.testing.assert_allclose(got, wanted, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_kernel_path_in_bfloat16_is_the_einsum_spelling(shape):
    """Values and gradients to bfloat16's rounding: both spellings multiply
    bfloat16 into float32 and take the softmax in float32; the kernels
    normalise after the second product and keep dp in float32."""
    e_qk, e_v, heads, kv_heads = shape
    *args, weight = _inputs(T, *shape, jnp.bfloat16)
    scale = e_qk ** -0.5
    out = _kernel(*args, scale)
    value, grads = _scored(_kernel, weight, scale)(*args)
    want_out = _whole(*args, scale)
    want, want_grads = _scored(_whole, weight, scale)(*args)
    assert out.dtype == want_out.dtype == jnp.bfloat16
    assert _gap(out, want_out) < 4e-3            # half a unit in the last
    np.testing.assert_allclose(value, want, rtol=2e-2, atol=0.5)
    for got, wanted in zip(grads, want_grads):
        assert got.dtype == wanted.dtype == jnp.bfloat16
        assert _gap(got, wanted) < 1e-2


@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_a_ragged_last_block_is_masked(shape):
    """``flash_tiles`` admits no ragged length; ring attention's hops and
    the operator may hand the kernels one: 320 positions in blocks of 128,
    the padding of the last block of queries and of keys left out of every
    sum."""
    e_qk, e_v, heads, kv_heads = shape
    t = 320
    assert pk.flash_tiles(t, heads, kv_heads, e_qk, e_v, jnp.float32) is None
    *args, weight = _inputs(t, *shape, jnp.float32)
    scale = e_qk ** -0.5
    heads_first = lambda a: a.transpose(0, 2, 1, 3)

    def ragged(q, k, v, scale):
        return heads_first(pk.flash_mha(*map(heads_first, (q, k, v)), True,
                                        scale, (128, 128)))

    value, grads = _scored(ragged, weight, scale)(*args)
    with jax.default_matmul_precision("highest"):
        want, want_grads = _scored(_whole, weight, scale)(*args)
    np.testing.assert_allclose(value, want, rtol=2e-5)
    for got, wanted in zip(grads, want_grads):
        np.testing.assert_allclose(got, wanted, rtol=2e-4, atol=2e-5)


# -- (b) which shapes take the kernels ----------------------------------------
@pytest.mark.parametrize("t,heads,kv_heads,e_qk,e_v,dtype,blocks", [
    (8192, 32, 32, 192, 128, jnp.bfloat16, (1024, 1024)),   # the JoyAI cell
    (4096, 32, 8, 64, 64, jnp.bfloat16, (512, 1024)),       # the Granite cell
    (8192, 32, 32, 192, 128, jnp.float32, (256, 1024)),     # float32: less
    (384, 4, 4, 192, 128, jnp.float32, (128, 128)),
    (64, 4, 4, 12, 8, jnp.bfloat16, None),      # JoyAI's rehearsal size
    (32, 4, 2, 8, 8, jnp.bfloat16, None),       # Granite's rehearsal size
    (4096, 32, 8, 80, 80, jnp.bfloat16, None),  # a width of no half tile
    (4000, 32, 8, 64, 64, jnp.bfloat16, None),  # a length of no lane tiles
    (4096, 32, 5, 64, 64, jnp.bfloat16, None),  # heads that do not group
    (4096, 32, 8, 64, 64, jnp.float16, None),
])
def test_which_shapes_take_the_kernel(t, heads, kv_heads, e_qk, e_v, dtype,
                                      blocks):
    assert pk.flash_tiles(t, heads, kv_heads, e_qk, e_v, dtype) == blocks
    if blocks:
        assert pk._flash_vmem_bytes(
            *blocks, heads // kv_heads, e_qk, e_v,
            jnp.dtype(dtype).itemsize) <= pk.FLASH_VMEM_BYTES


def test_the_rehearsal_sizes_hold_no_kernel_and_the_blocks_are_the_same_map():
    """Where ``flash_tiles`` declines, ``causal_gqa_attention`` is blocks
    of rows under a checkpoint each: no Pallas call, the reference's
    numbers."""
    *args, weight = _inputs(64, 12, 8, 4, 2, jnp.float32)
    blocks = lambda q, k, v, scale: hybrid.causal_gqa_attention(
        q, k, v, scale, 16)
    jaxpr = jax.make_jaxpr(_scored(blocks, weight, 0.3))(*args).jaxpr
    assert _kernels(jaxpr) == []
    value, grads = _scored(blocks, weight, 0.3)(*args)
    want, want_grads = _scored(_whole, weight, 0.3)(*args)
    np.testing.assert_allclose(value, want, rtol=1e-5)
    for got, wanted in zip(grads, want_grads):
        np.testing.assert_allclose(got, wanted, rtol=1e-4, atol=1e-6)


# -- (c) what the traced gradient holds ---------------------------------------
def _attention_config(mixer, seq, **sizes):
    from mxnet_tpu.transformer import HybridLMConfig
    ffn = ("gated_mlp", "sparse_experts") if mixer == "latent_attention" \
        else ("gated_mlp", "gated_mlp")
    return HybridLMConfig(
        layer_types=(mixer, mixer), ffn_types=ffn, d_model=32, d_ff=64,
        n_heads=2, n_kv_heads=1, head_dim=64, qk_nope_dim=64, qk_rope_dim=64,
        v_head_dim=64, seq_len=seq, attention_block=32, **sizes)


def _traced_loss_gradient(cfg, seq):
    from mxnet_tpu.parallel import MeshPlan
    from mxnet_tpu.transformer import HybridLM
    program = HybridLM(cfg).mesh_program(MeshPlan(data=1))
    params = program.init_params()
    x = jnp.zeros((1, seq), jnp.int32)
    vals = tuple(params[n] for n in program.param_names)
    return jax.make_jaxpr(jax.grad(
        lambda vals: program.loss_replica(vals, x, x, None)))(vals).jaxpr


@pytest.mark.parametrize("mixer", ["attention", "latent_attention"])
def test_no_scores_reach_the_program_and_the_forward_kernel_runs_once(mixer):
    """The gradient of a two-layer model's loss, every layer under its
    checkpoint: each layer holds the forward kernel once (the layer's
    re-run reads the kept ``o`` and ``lse`` and drops the kernel) and each
    backward kernel once, and no array has two axes of the sequence."""
    seq = 256
    jaxpr = _traced_loss_gradient(_attention_config(mixer, seq), seq)
    assert sorted(_kernels(jaxpr)) == sorted(
        ["_fa_kernel", "_fa_dq_kernel", "_fa_dkv_kernel"] * 2)
    squares = [eqn for eqn in _equations(jaxpr) for v in eqn.outvars
               if list(getattr(v.aval, "shape", ())).count(seq) >= 2]
    assert not squares


def test_the_blocks_hold_the_scores_the_kernels_do_not():
    """The detector's own proof: at a length the kernels decline, the
    blocks' scores are in the traced gradient."""
    seq = 64
    jaxpr = _traced_loss_gradient(_attention_config("attention", seq), seq)
    assert _kernels(jaxpr) == []
    assert any(tuple(v.aval.shape)[-2:] == (32, seq)
               for eqn in _equations(jaxpr) for v in eqn.outvars
               if hasattr(v.aval, "shape"))


# -- (d) under a data mesh ----------------------------------------------------
@pytest.mark.parametrize("mixer", ["attention", "latent_attention"])
def test_kernel_under_a_data_axis_of_two_matches_one_device(mixer):
    """The mesh step is one ``shard_map`` program: the kernels' results
    carry their inputs' varying axes, and two replicas of half the batch
    take the step one replica of the whole batch takes."""
    from mxnet_tpu.parallel import DataParallelTrainer, MeshPlan
    from mxnet_tpu.telemetry import compiles
    from mxnet_tpu.transformer import HybridLM
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    seq = 128
    cfg = _attention_config(mixer, seq)
    ids = np.random.RandomState(0).randint(
        0, 64, (2, seq + 1)).astype(np.int32)
    losses = {}
    for data in (1, 2):
        before = compiles.counters()["flash_attention_layers"]
        trainer = DataParallelTrainer(
            HybridLM(cfg), None, "sgd",
            {"learning_rate": 0.05, "momentum": 0.9},
            mesh_plan=MeshPlan(data=data))
        losses[data] = [float(trainer.step(ids[:, :-1], ids[:, 1:]).asnumpy())
                        for _ in range(2)]
        trainer.flush()          # leave no step in flight for a later test
        assert compiles.counters()["flash_attention_layers"] > before
    np.testing.assert_allclose(losses[2], losses[1], rtol=1e-5)
    assert losses[1][1] < losses[1][0]


# -- (e) the counter and the doctor's line ------------------------------------
@pytest.mark.parametrize("mixer,seq,kernel_layers", [
    ("attention", 128, 2), ("latent_attention", 128, 2),
    ("attention", 64, 0), ("latent_attention", 64, 0)])
def test_kernel_layers_counter_and_the_doctors_line(mixer, seq,
                                                    kernel_layers):
    from mxnet_tpu import telemetry
    from mxnet_tpu.parallel import MeshPlan
    from mxnet_tpu.telemetry import compiles
    from mxnet_tpu.transformer import HybridLM
    program = HybridLM(_attention_config(mixer, seq)).mesh_program(
        MeshPlan(data=1))
    params = program.init_params()
    before = compiles.counters()
    x = jnp.zeros((1, seq), jnp.int32)
    jaxpr = jax.make_jaxpr(program.loss_replica)(
        tuple(params[n] for n in program.param_names), x, x, None).jaxpr
    after = compiles.counters()
    traced = {k: after[k] - before[k] for k in after}
    assert traced["attention_layers"] == 2
    assert traced["flash_attention_layers"] == kernel_layers
    assert _kernels(jaxpr) == ["_fa_kernel"] * kernel_layers
    text = telemetry.render_doctor({
        "directory": "d", "ranks": {"worker0": {"compiles": traced}},
        "stragglers": [], "events": dict.fromkeys(
            ("straggler", "anomaly", "queue_growth", "fault"), ())})
    assert ("2 attention layer(s) in the traced programs, the scores as "
            "Pallas flash kernels in %d of them" % kernel_layers) in text


# -- (f) the memory reckoning -------------------------------------------------
def _cell_config(name):
    from mxnet_tpu.transformer import HybridLMConfig
    path = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                        "configs", name + ".json")
    with open(path) as f:
        config = json.load(f)
    if config["family"] == "mla_moe":
        shard = config["expert_shard"]
        return HybridLMConfig.from_hf(
            dict(config, n_routed_experts=config["n_routed_experts"]
                 * shard["of"]),
            seq_len=config["seq_len"],
            attention_block=config["attention_block"],
            expert_shard=(shard["index"], shard["of"]))
    return HybridLMConfig.from_hf(config, seq_len=config["seq_len"])


@pytest.mark.parametrize("cell,mixer,params,batch,keeps", [
    ("JoyAI-LLM-Flash", "latent_attention", 680.5e6, 2, False),
    ("granite-4.0-h-micro", "attention", 772.2e6, 1, True)])
def test_the_cells_memory_decisions_stay(cell, mixer, params, batch, keeps):
    """With the scores out of an attention layer's reckoned live set both
    cells decide as before: JoyAI does not keep its products, Granite
    does."""
    cfg = _cell_config(cell)
    bf16 = jnp.bfloat16
    seq = cfg.seq_len
    assert hybrid.attention_kernel_blocks(cfg, mixer, seq, bf16)
    kernels = hybrid._layer_live_bytes(cfg, mixer, batch, seq, bf16)
    cfg.seq_len = seq                           # a length the kernels decline
    blocks = hybrid._layer_live_bytes(cfg, mixer, batch, seq - 64, bf16)
    scores = cfg.n_heads * cfg.attention_block * (seq - 64) \
        * (1 if mixer == "latent_attention" else batch)
    assert blocks > kernels and blocks - 4 * 4 * scores < kernels
    assert hybrid.keeps_products(cfg, params, batch, seq, bf16,
                                 16.9e9) is keeps


# -- (g) the declared costs ---------------------------------------------------
def test_declared_costs_of_the_three_kernels():
    """One latent-attention layer of the JoyAI cell: the products the
    kernels run over every pair (causality not discounted), an exponential
    a pair, one pass over operands and results with the keys and values
    once a block of queries (the queries once a block of keys)."""
    from mxnet_tpu.analysis import lint_kernel_costs
    from mxnet_tpu.analysis.cost import KERNEL_COSTS, kernel_name_of
    assert lint_kernel_costs() == []
    b, heads, t, e_qk, e_v = 2, 32, 8192, 192, 128
    bf16 = jnp.bfloat16
    q = jax.ShapeDtypeStruct((b, heads, t, e_qk), bf16)
    v = jax.ShapeDtypeStruct((b, heads, t, e_v), bf16)
    blocks = pk.flash_tiles(t, heads, heads, e_qk, e_v, bf16)
    jaxpr = jax.make_jaxpr(lambda q, k, v: jax.vjp(
        lambda *a: pk.flash_mha(*a, True, 0.07, blocks), q, k, v)[1](
            jnp.ones(v.shape, bf16)))(q, q, v).jaxpr
    costs = {kernel_name_of(eqn): KERNEL_COSTS[kernel_name_of(eqn)](eqn)
             for eqn in _equations(jaxpr)
             if eqn.primitive.name == "pallas_call"}
    pairs = b * heads * t * t
    assert costs["_fa_kernel"]["flops"] == 2 * pairs * (e_qk + e_v)
    assert costs["_fa_dq_kernel"]["flops"] == 2 * pairs * (2 * e_qk + e_v)
    assert costs["_fa_dkv_kernel"]["flops"] == 4 * pairs * (e_qk + e_v)
    assert costs["_fa_kernel"]["transcendentals"] == pairs + pairs // t
    q_bytes, v_bytes, rows = 2 * b * heads * t * e_qk, \
        2 * b * heads * t * e_v, 4 * b * heads * t
    n = t // blocks[0]
    assert costs["_fa_kernel"]["bytes_read"] \
        == q_bytes + n * (q_bytes + v_bytes)
    assert costs["_fa_kernel"]["bytes_written"] == v_bytes + rows
    assert costs["_fa_dq_kernel"]["bytes_read"] \
        == q_bytes + v_bytes + 2 * rows + n * (q_bytes + v_bytes)
    assert costs["_fa_dkv_kernel"]["bytes_read"] \
        == q_bytes + v_bytes + n * (q_bytes + v_bytes + 2 * rows)
    assert costs["_fa_dkv_kernel"]["bytes_written"] == q_bytes + v_bytes
