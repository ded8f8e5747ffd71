"""The chunked delta rule as a Pallas kernel pair (``ops/kda_kernels.py``):
the same map as ``kda.kda_recurrence`` in values and in the gradients of all
five operands, at the tile of the ``Ling-3.0-flash`` cell (chunks of 64,
heads of 128 columns); the three hard cases ``tests/test_kda_lm.py`` has for
``kda.kda_chunked``; which shapes take the kernels; what the traced gradient
of a layer holds; the counter and the doctor's line; the reckoned memory;
the declared costs.  That Mosaic takes both kernels at the cell's shape is in
``tests/test_ssd_kernel.py``, the one file that describes a v5e.

On the CPU the kernels run in the Pallas interpreter
(``pallas_kernels.resolve_interpret``)."""
from __future__ import annotations

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import kda_kernels
from mxnet_tpu.transformer import HybridLMConfig, hybrid, kda

WIDTH = 128
ALL = (0, 1, 2, 3, 4)                             # q, k, v, g, beta
CELL = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                    "benchmark", "configs", "Ling-3.0-flash.json")


def _family():
    """The cell's family, loaded by path as ``run.py`` loads it."""
    bench = os.path.normpath(os.path.join(os.path.dirname(CELL), ".."))
    if bench not in sys.path:
        sys.path.insert(0, bench)
    spec = importlib.util.spec_from_file_location(
        "benchmark_families_kda_mla_moe",
        os.path.join(bench, "families", "kda_mla_moe.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _close(a, b, tol):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert np.isfinite(a).all()
    assert np.abs(a - b).max() <= tol * max(np.abs(b).max(), 1e-30), \
        np.abs(a - b).max() / np.abs(b).max()


def _scan_inputs(seed, t, heads=2, batch=1, g=None):
    """As ``tests/test_kda_lm.py``'s: q and k of unit length a head (q
    scaled), the gate spread over (-5, 0) or all at ``g``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (batch, t, heads, WIDTH)
    q = kda._l2_normed(jax.random.normal(ks[0], shape)) * WIDTH ** -0.5
    k = kda._l2_normed(jax.random.normal(ks[1], shape))
    v = jax.random.normal(ks[2], shape)
    gate = -5 * jax.nn.sigmoid(3 * jax.random.normal(ks[3], shape) - 2) \
        if g is None else jnp.full(shape, g, jnp.float32)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3]))
    return q, k, v, gate, beta


def _grads(fn, weight, args):
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weight),
                    argnums=ALL)(*args)


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def _kernels(jaxpr):
    return [eqn.params["name"] for eqn in _equations(jaxpr)
            if eqn.primitive.name == "pallas_call"]


# -- the kernels are the recurrence ------------------------------------------
@pytest.mark.parametrize("heads,chunks,batch", [(2, 3, 2), (4, 6, 1),
                                                (8, 2, 1), (16, 2, 1)])
def test_kernels_are_the_recurrence_in_values_and_gradients(heads, chunks,
                                                            batch):
    """float32, two to eight heads and sixteen (two steps of the grid), two
    to six chunks, one and two sequences: the recurrence's output and its
    gradients by all five operands, to rounding."""
    args = _scan_inputs(heads, 64 * chunks, heads, batch)
    assert kda_kernels.tiles(64, heads, WIDTH, jnp.float32)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    _close(kda_kernels.kda_scan(*args), kda.kda_recurrence(*args), 1e-5)
    for got, wanted in zip(_grads(kda_kernels.kda_scan, weight, args),
                           _grads(kda.kda_recurrence, weight, args)):
        _close(got, wanted, 2e-5)


def test_kernels_in_bfloat16_are_as_near_the_recurrence_as_the_chunked_form():
    """Operands of the products in bfloat16, the gate, its sums, the system
    and the state in float32: within the 3% in value and 5% in every
    gradient that ``kda_chunked`` is held to."""
    args = _scan_inputs(7, 200)
    low = tuple(a.astype(jnp.bfloat16) for a in args[:3]) + args[3:]
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    got = kda_kernels.kda_scan(*low)
    assert got.dtype == jnp.bfloat16
    _close(got, kda.kda_recurrence(*args), 3e-2)
    got_grads = _grads(kda_kernels.kda_scan, weight, low)
    assert [g.dtype for g in got_grads] == [jnp.bfloat16] * 3 \
        + [jnp.float32] * 2
    for got, wanted in zip(got_grads,
                           _grads(kda.kda_recurrence, weight, args)):
        _close(got, wanted, 5e-2)


def test_every_gradient_in_bfloat16_is_as_near_as_the_chunked_forms():
    """Against the recurrence on the same rounded operands, by the norm of
    the whole difference: no gradient of the kernels is further out than
    ``kda_chunked``'s.  The gate's is the one that tells: without the
    reference sums' own share (``r_a`` takes what its columns give less
    what its rows give: nothing on paper) a chunk's ``dG`` no longer adds up
    under bfloat16 operands, the sum to the chunk's end carries the residue
    to every earlier token, and ``dg`` read 1.85 times the chunked form's
    error (0.76 with it)."""
    args = _scan_inputs(11, 256)
    low = tuple(a.astype(jnp.bfloat16) for a in args[:3]) + args[3:]
    rounded = tuple(a.astype(jnp.float32) for a in low)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    want = _grads(kda.kda_recurrence, weight, rounded)
    off = lambda got: [float(jnp.linalg.norm(
        (a.astype(jnp.float32) - b).ravel()) / jnp.linalg.norm(b.ravel()))
        for a, b in zip(got, want)]
    kernels = off(_grads(kda_kernels.kda_scan, weight, low))
    chunked = off(_grads(lambda *a: kda.kda_chunked(*a, 64), weight, low))
    for name, ours, theirs in zip("q k v g beta".split(), kernels, chunked):
        assert ours <= 1.05 * theirs, (name, ours, theirs)


# -- the three hard cases of the chunked form --------------------------------
@pytest.mark.parametrize("g", [-5.0, 0.0])
def test_a_gate_at_its_bound_stays_finite_and_exact(g):
    """Every g = -5 for two whole chunks (a chunk decays by e^-320; the
    factors relative to the middle of a sub-block stay within e^+-40), and
    no decay at all."""
    args = _scan_inputs(128, 128, g=g)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    _close(kda_kernels.kda_scan(*args), kda.kda_recurrence(*args), 1e-5)
    for got, wanted in zip(_grads(kda_kernels.kda_scan, weight, args),
                           _grads(kda.kda_recurrence, weight, args)):
        _close(got, wanted, 1e-4)


@pytest.mark.parametrize("noise,beta,g", [(0.1, 0.9, -0.001),
                                          (0.01, 0.99, -0.0001)])
def test_keys_that_point_the_same_way_stay_exact(noise, beta, g):
    """A chunk whose keys all but coincide at ``beta`` 0.9: the nilpotent
    product read 3e18 off here; the substitution and the halves stay at
    rounding, in value and gradients."""
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    shape = (1, 128, 2, WIDTH)
    base = jax.random.normal(ks[0], (1, 1, 2, WIDTH))
    k = kda._l2_normed(base + noise * jax.random.normal(ks[1], shape))
    q = kda._l2_normed(base + noise * jax.random.normal(ks[2], shape)) / 4
    v = jax.random.normal(ks[3], shape)
    args = (q, k, v, jnp.full(shape, g), jnp.full(shape[:3], beta))
    weight = jax.random.normal(ks[4], shape)
    _close(kda_kernels.kda_scan(*args), kda.kda_recurrence(*args), 1e-5)
    for got, wanted in zip(_grads(kda_kernels.kda_scan, weight, args),
                           _grads(kda.kda_recurrence, weight, args)):
        _close(got, wanted, 5e-4)


def test_a_sequence_that_is_no_multiple_of_the_chunk():
    """200 tokens are three chunks and eight tokens: padded at the end with
    tokens no earlier one sees, in values and gradients."""
    args = _scan_inputs(200, 200)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    _close(kda_kernels.kda_scan(*args), kda.kda_recurrence(*args), 1e-5)
    for got, wanted in zip(_grads(kda_kernels.kda_scan, weight, args),
                           _grads(kda.kda_recurrence, weight, args)):
        assert got.shape == wanted.shape
        _close(got, wanted, 2e-5)


# -- which shapes take the kernels -------------------------------------------
@pytest.mark.parametrize("chunk,heads,width,dtype,takes", [
    (64, 32, 128, "bfloat16", True),              # Ling-3.0-flash.tokens
    (64, 32, 128, "float32", True),
    (64, 2, 128, "float32", True),                # fewer heads than a step
    (64, 8, 128, "bfloat16", True),
    (64, 6, 128, "bfloat16", True),
    (64, 12, 128, "bfloat16", False),             # no whole steps
    (16, 4, 8, "float32", False),                 # tests/test_kda_lm.py SMALL
    (8, 4, 8, "float32", False),                  # HybridLMConfig's defaults
    (64, 32, 64, "bfloat16", False),
    (128, 32, 128, "bfloat16", False),
    (64, 32, 128, "float16", False),
])
def test_tiles_table(chunk, heads, width, dtype, takes):
    assert kda_kernels.tiles(chunk, heads, width, jnp.dtype(dtype)) is takes
    # a pure function: asked again, the same answer
    assert kda_kernels.tiles(chunk, heads, width, jnp.dtype(dtype)) is takes
    assert kda_kernels.SUB_BLOCK == kda.SUB_BLOCK
    assert kda_kernels.heads_per_step(heads) in (heads,
                                                 kda_kernels.STEP_HEADS)


# -- what the traced gradient of a layer holds -------------------------------
def _layer_config(**sizes):
    """Layers of the cell's linear-attention tile at a small model width."""
    sizes = {**dict(n_heads=32, kda_head_dim=WIDTH, kda_chunk=64,
                    n_kv_heads=32), **sizes}
    return HybridLMConfig(
        vocab_size=64, d_model=64, d_ff=64, seq_len=128, kda_conv=4,
        tie_embeddings=False, **sizes)


def _mixer_leaves(cfg, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), len(kda.leaves(cfg)))
    return {kind: (0.05 * jax.random.normal(key, shape)).astype(dtype)
            for key, (kind, shape) in zip(ks, kda.leaves(cfg))}


def test_a_layers_gradient_holds_the_forward_kernel_twice_and_no_while():
    """One Ling-shaped mixer (32 heads of 128, chunks of 64) under the
    layer's checkpoint: the step's forward pass and the layer's re-run are
    the forward kernel (both as the differentiated rule, so both write the
    handed states; a call that is not differentiated writes none), the
    backward pass the backward kernel once, and nothing under the scope
    ``kda_scan`` is a loop."""
    cfg = _layer_config()
    bf16 = jnp.bfloat16
    leaves = _mixer_leaves(cfg, bf16)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 128, 64)).astype(bf16)
    layer = jax.checkpoint(lambda lp, x: kda.kda_mixer(lp, x, cfg))
    loss = lambda lp, x: layer(lp, x).astype(jnp.float32).sum()
    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss))(leaves, x).jaxpr
    assert sorted(_kernels(jaxpr)) == ["_kda_scan_bwd_kernel"] \
        + ["_kda_scan_fwd_kernel"] * 2
    states = [len(eqn.outvars) for eqn in _equations(jaxpr)
              if eqn.primitive.name == "pallas_call"
              and eqn.params["name"] == "_kda_scan_fwd_kernel"]
    assert states == [2, 2]
    alone = jax.make_jaxpr(lambda lp, x: kda.kda_mixer(lp, x, cfg))(
        leaves, x).jaxpr
    assert [len(eqn.outvars) for eqn in _equations(alone)
            if eqn.primitive.name == "pallas_call"] == [1]
    loops = [eqn for eqn in _equations(jaxpr)
             if eqn.primitive.name in ("while", "scan")
             and "kda_scan" in str(eqn.source_info.name_stack)]
    assert not loops
    # the rehearsal shapes keep the chunked form and its scans
    small = _layer_config(n_heads=4, kda_head_dim=8, kda_chunk=16,
                          n_kv_heads=4)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        lambda lp, x: kda.kda_mixer(lp, x, small).sum()))(
        _mixer_leaves(small, jnp.float32),
        x.astype(jnp.float32)).jaxpr
    assert not _kernels(jaxpr)
    assert any(eqn.primitive.name == "scan" for eqn in _equations(jaxpr))


def test_the_mixer_on_the_kernels_is_the_mixer_on_the_chunked_form(
        monkeypatch):
    """The whole mixer in float32, once with the kernels and once with
    ``tiles`` refusing: the same output and the same gradients of every
    leaf (to 5e-4 of a leaf's largest entry: a small leaf's gradient is a
    sum over every token that all but cancels, and the two spellings round
    its terms each their own way)."""
    cfg = _layer_config(n_heads=4, n_kv_heads=4)
    leaves = _mixer_leaves(cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 128, 64))
    weight = jax.random.normal(jax.random.PRNGKey(5), (2, 128, 64))
    scored = jax.value_and_grad(
        lambda lp, x: jnp.sum(kda.kda_mixer(lp, x, cfg) * weight),
        argnums=(0, 1))
    assert kda.scan_kernel_tiles(cfg, jnp.float32)
    got, (got_leaves, got_x) = scored(leaves, x)
    monkeypatch.setattr(kda_kernels, "tiles", lambda *a: False)
    want, (want_leaves, want_x) = scored(leaves, x)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    _close(got_x, want_x, 5e-5)
    for kind in leaves:
        _close(got_leaves[kind], want_leaves[kind], 5e-4)


# -- the counter and the doctor's line ---------------------------------------
@pytest.mark.parametrize("chunk,kernel_layers", [(64, 6), (32, 0)])
def test_kernel_layers_counter_and_the_doctors_line(chunk, kernel_layers):
    """Seven layers in the cell's order (five linear, one latent, one
    linear) at the cell's tile: 6 of 6 on the kernels; at a chunk the rule
    refuses, none."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.parallel import MeshPlan
    from mxnet_tpu.telemetry import compiles
    from mxnet_tpu.transformer import HybridLM
    cfg = _layer_config(
        n_heads=4, n_kv_heads=4, kda_chunk=chunk,
        layer_types=("linear_attention",) * 5 + ("latent_attention",
                                                 "linear_attention"))
    program = HybridLM(cfg).mesh_program(MeshPlan(data=1))
    params = program.init_params()
    before = compiles.counters()
    x = jnp.zeros((1, 128), jnp.int32)
    jaxpr = jax.make_jaxpr(program.loss_replica)(
        tuple(params[n] for n in program.param_names), x, x, None).jaxpr
    after = compiles.counters()
    traced = {k: after[k] - before[k] for k in after}
    assert traced["linear_attention_layers"] == 6
    assert traced["kda_kernel_layers"] == kernel_layers
    assert len(_kernels(jaxpr)) == kernel_layers
    traced["kda_chunks_per_seq"] = after["kda_chunks_per_seq"]
    text = telemetry.render_doctor({
        "directory": "d", "ranks": {"worker0": {"compiles": traced}},
        "stragglers": [], "events": dict.fromkeys(
            ("straggler", "anomaly", "queue_growth", "fault"), ())})
    assert ("6 linear-attention layer(s) in the traced programs, the delta "
            "rule in %d chunk(s) a sequence, as a Pallas kernel pair in %d "
            "of them" % (128 // chunk, kernel_layers)) in text


# -- the reckoned memory ------------------------------------------------------
def test_the_cells_live_set_is_the_handed_states_and_the_decision_stays(
        monkeypatch):
    """At the cell's size a linear-attention layer on the kernels holds the
    state every chunk was handed (256 chunks x 32 heads x 128 x 128 float32:
    0.54 GB) and five gradients, no block of ``SCAN_BLOCK_CHUNKS`` chunks;
    the products are not kept on a v5e either way."""
    with open(CELL) as f:
        config = json.load(f)
    family = _family()
    keys, sizes = family.program_keys(config)
    cfg = HybridLMConfig.from_hf(keys, **sizes)
    bf16 = jnp.bfloat16
    assert kda.scan_kernel_tiles(cfg, bf16)
    live = lambda seq: hybrid._layer_live_bytes(
        cfg, "linear_attention", 1, seq, bf16, "sparse_experts")
    kernels, half = live(16384), live(8192)
    monkeypatch.setattr(kda_kernels, "tiles", lambda *a: False)
    chunked, chunked_half = live(16384), live(8192)
    states = 256 * 32 * 128 * 128 * 4
    gradients = 16384 * 4096 * (3 * 2 + 4)
    block = 2048 * (10 * 4096 + 12 * 64 * 32) // 2 * 16
    assert states == 536_870_912
    assert kernels - chunked == states + gradients - block
    # the states grow with the sequence; a block of 32 chunks does not
    assert (kernels - half) - (chunked - chunked_half) \
        == (states + gradients) // 2
    assert 1.0e9 < kernels < 4.0e9
    for tiles in (lambda *a: False, kda_kernels.__dict__["tiles"]):
        monkeypatch.setattr(kda_kernels, "tiles", tiles)
        assert not hybrid.keeps_products(cfg, 822_060_224, 1, 16384, bf16,
                                         16.9e9)
        assert hybrid.keeps_products(cfg, 822_060_224, 1, 16384, bf16,
                                     33.8e9)


# -- the declared costs -------------------------------------------------------
def test_declared_costs_of_both_kernels():
    """One layer's scan of the cell: the products the kernels run and one
    pass over operands and results; the lint finds nothing undeclared."""
    from mxnet_tpu.analysis import lint_kernel_costs
    from mxnet_tpu.analysis.cost import KERNEL_COSTS, kernel_name_of
    assert lint_kernel_costs() == []
    b, t, h, e = 1, 16384, 32, WIDTH
    bf16, f32 = jnp.bfloat16, jnp.float32
    shapes = (jax.ShapeDtypeStruct((b, t, h, e), bf16),) * 3 + (
        jax.ShapeDtypeStruct((b, t, h, e), f32),
        jax.ShapeDtypeStruct((b, t, h), f32))
    jaxpr = jax.make_jaxpr(lambda *a: jax.vjp(kda_kernels.kda_scan, *a)[1](
        jnp.ones((b, t, h, e), bf16)))(*shapes).jaxpr
    costs = {kernel_name_of(eqn): KERNEL_COSTS[kernel_name_of(eqn)](eqn)
             for eqn in _equations(jaxpr)
             if eqn.primitive.name == "pallas_call"}
    fwd, bwd = (costs["_kda_scan_%s_kernel" % k] for k in ("fwd", "bwd"))
    rows, size = b * t * h, 64
    wide, square, full = size * e, e * e, size * size
    row = 16 * size                               # the substitution's rows
    assert fwd["flops"] == 2 * rows * (4 * wide + 3 * square + 6 * wide
                                       + 24 * full + row)
    assert bwd["flops"] == 2 * rows * (13 * wide + 7 * square + 9 * wide
                                       + 36 * full + row)
    assert fwd["transcendentals"] == bwd["transcendentals"] == rows * e * 8
    operands = rows * (3 * e * 2 + e * 4 + 4)
    states = (t // size) * h * e * e * 4          # what each chunk was handed
    assert fwd["bytes_read"] == operands
    assert fwd["bytes_written"] == rows * e * 2 + states
    assert bwd["bytes_read"] == operands + states + rows * e * 2
    assert bwd["bytes_written"] == rows * (3 * e * 2 + e * 4 + 4)
