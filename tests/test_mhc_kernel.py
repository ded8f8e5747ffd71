"""The hyper-connection around a sublayer as two Pallas kernel pairs
(``ops/mhc_kernels.py``): the same map as ``transformer/mhc.py``'s
``jax.numpy`` spelling (``coefficients``, ``read_in``, ``write_out``) in
values and in the gradients by the stream, the sublayer's result and the
three leaves; logits at and past Sinkhorn's clamp; bfloat16 against float32;
both against the benchmark's plain reference; which shapes take the kernels, and that a refusal leaves the
spelling; the counter; the declared costs.  That Mosaic takes the kernels
at the cell's widths is in ``tests/test_ssd_kernel.py``, the one file that
describes a v5e.

On the CPU the kernels run in the Pallas interpreter
(``pallas_kernels.resolve_interpret``); every test here shares one token
count, one width and one set of blocks, so the kernels are traced and
compiled once a dtype."""
from __future__ import annotations

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import mhc_kernels
from mxnet_tpu.transformer import HybridLMConfig, mhc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
N, C = 4, 256
B, T = 2, 150                      # 300 tokens: two blocks of 256, padded
# two column blocks of a stream (``mhc_tiles`` would take it whole)
TILES = (256, 128)
ALL = tuple(range(5))              # x, y, phi, bias, alpha


def _cfg(**kw):
    # four of Sinkhorn's iterations: the kernels loop over them, the
    # spelling unrolls them, and four cost its compile less than twenty
    sizes = dict(d_model=C, hc_mult=N, hc_sinkhorn_iters=4, hc_eps=1e-6,
                 norm_eps=1e-6)
    sizes.update(kw)
    return HybridLMConfig(layer_types=("latent_attention",), **sizes)


CFG = _cfg()
SETTINGS = (CFG.norm_eps, CFG.hc_res_clamp, CFG.hc_sinkhorn_iters,
            CFG.hc_eps)


def _inputs(seed, dtype=jnp.float32, clamp=None):
    """The stream, the sublayer's result and one connection's leaves;
    ``clamp``: every token's ``G_res`` logits ``+-clamp``, one large a row
    and column (``phi``'s columns of ``res`` zero, the biases the logits).
    Drawn by numpy: no program to compile."""
    rs = np.random.RandomState(seed)
    width = N * N + 2 * N
    x = rs.standard_normal((B, T, N, C))
    y = rs.standard_normal((B, T, C))
    phi = rs.standard_normal((N * C, width)) / np.sqrt(N * C)
    bias = rs.uniform(-1, 1, width)
    alpha = rs.uniform(0.5, 1.5, 3)
    if clamp is not None:
        logits = np.full((N, N), -clamp)
        logits[np.arange(N), rs.permutation(N)] = clamp
        phi[:, 2 * N:] = 0.0
        bias[2 * N:] = logits.ravel()
    weights = (rs.standard_normal((B, T, C)).astype(np.float32),
               rs.standard_normal((B, T, N, C)).astype(np.float32))
    return tuple(jnp.asarray(a, dtype) for a in (x, y, phi, bias, alpha)), \
        weights


def _kernels(x, y, phi, bias, alpha):
    flat = mhc_kernels.whole_blocks(x.reshape(B * T, N * C), TILES)
    u, h, flat = mhc_kernels.connect_in(flat, phi, bias, alpha, TILES,
                                        SETTINGS)
    out = mhc_kernels.connect_out(
        flat, mhc_kernels.whole_blocks(y.reshape(B * T, C), TILES), h, TILES)
    return u[:B * T].reshape(B, T, C), out[:B * T].reshape(x.shape)


def _spelled(x, y, phi, bias, alpha):
    h_pre, h_post, h_res = mhc.coefficients(phi, bias, alpha, x, CFG)
    return mhc.read_in(h_pre, x), mhc.write_out(h_res, h_post, x, y)


def _scored(fn):
    """``fn``'s ``u`` and ``x'``, and the value and the gradients by all
    five of ``sum(u w_u) + sum(sin(x') w_x)`` (the sine keeps ``dx'`` from
    being ``w_x`` alone), as one program."""
    def loss(args, weights):
        u, out = fn(*args)
        return jnp.sum(u.astype(jnp.float32) * weights[0]) + jnp.sum(
            jnp.sin(out.astype(jnp.float32)) * weights[1])

    return jax.jit(lambda args, weights: (
        fn(*args), jax.value_and_grad(loss)(args, weights)))


KERNELS, SPELLED = _scored(_kernels), _scored(_spelled)


def _off(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert a.shape == b.shape and np.isfinite(a).all()
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


# -- the kernels are the spelling ---------------------------------------------
@pytest.mark.parametrize("clamp", [None, 30.0, 1e4],
                         ids=["seeded", "at_the_clamp", "past_the_clamp"])
def test_both_kernel_pairs_are_the_spelling_in_values_and_gradients(clamp):
    """float32, 300 tokens (two token blocks, the second padded) and four
    column blocks of the coefficients' reduction: ``u``, ``x'`` and the
    gradients by x, y, phi, bias and alpha to 1e-5 of each one's largest
    entry.  With ``G_res`` at the clamp of +-30 (where ``jnp.clip`` splits
    its slope) or far past it, ``H_res`` is a permutation within float32 and
    every gradient through the kernel's Sinkhorn backward is finite and the
    spelling's."""
    assert TILES == (256, 128)
    args, weights = _inputs(3 if clamp is None else 4, clamp=clamp)
    got, (got_loss, got_grads) = KERNELS(args, weights)
    want, (want_loss, want_grads) = SPELLED(args, weights)
    for g, w in zip(got, want):
        assert _off(g, w) <= 1e-5
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-5)
    for name, g, w in zip(("x", "y", "phi", "bias", "alpha"), got_grads,
                          want_grads):
        assert g.dtype == w.dtype
        assert _off(g, w) <= 1e-5, name
    if clamp is not None:
        _, h, _ = mhc_kernels.connect_in(
            mhc_kernels.whole_blocks(args[0].reshape(B * T, N * C), TILES),
            *args[2:], TILES, SETTINGS)
        h_res = np.asarray(h[:B * T, 2 * N:2 * N + N * N]).reshape(-1, N, N)
        np.testing.assert_allclose(h_res, np.round(h_res), atol=1e-5)


def test_kernels_in_bfloat16_are_no_further_from_float32_than_the_spelling():
    """bfloat16 stream, result and leaves: against the spelling in float32
    on the same rounded operands, ``u``, ``x'`` and every gradient within
    bfloat16's rounding (2%) and no further out than the spelling in
    bfloat16 itself, but for the rounding of the product's cotangent to
    the matrix unit's operand (2e-3)."""
    args, weights = _inputs(5, dtype=jnp.bfloat16)
    wide = tuple(a.astype(jnp.float32) for a in args)
    flat = lambda out: tuple(out[0]) + tuple(out[1][1])
    want = flat(SPELLED(wide, weights))
    got = flat(KERNELS(args, weights))
    spelled = flat(SPELLED(args, weights))
    assert [g.dtype for g in got] == [jnp.bfloat16] * 7
    for g, s, w in zip(got, spelled, want):
        assert _off(g, w) <= 2e-2
        assert _off(g, w) <= _off(s, w) + 2e-3


def test_both_kernel_pairs_are_the_references():
    """Against the family's ``hyper_connection`` (the equations as written,
    Sinkhorn as a plain loop, the mixes as sums over ``j``; ``u`` read where
    it is handed to the sublayer, whose result is ``y``) in float32 at
    ``highest``, as ``test_mhc.py::test_a_connection_is_the_references_in_
    values_and_gradients`` holds the spelling: ``u``, ``x'`` and the
    gradients by all five within 2e-5 of the largest."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "benchmark_families_mhc_mla_moe",
        os.path.join(BENCH, "families", "mhc_mla_moe.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    plain = {"hc_mult": N, "rms_norm_eps": CFG.norm_eps,
             "hc_sinkhorn_iters": CFG.hc_sinkhorn_iters,
             "hc_eps": CFG.hc_eps, "mhc_h_res_clamp_min": -30,
             "mhc_h_res_clamp_max": 30}

    def reference(x, y, phi, bias, alpha):
        lp = {"hc_mix_phi": phi, "hc_mix_bias": bias, "hc_mix_alpha": alpha}
        handed = []
        out = ref.hyper_connection(plain, lp, "hc_mix_", x,
                                   lambda u: handed.append(u) or y,
                                   ref.HOLD["float32"])
        return handed[0], out

    args, weights = _inputs(3)
    got, (got_loss, got_grads) = KERNELS(args, weights)
    with jax.default_matmul_precision("highest"):
        want, (want_loss, want_grads) = _scored(reference)(args, weights)
    for g, w in zip(got, want):
        assert _off(g, w) <= 2e-5
    assert float(got_loss) == pytest.approx(float(want_loss), rel=1e-5)
    for name, g, w in zip(("x", "y", "phi", "bias", "alpha"), got_grads,
                          want_grads):
        assert _off(g, w) <= 2e-5, name


# -- which shapes take the kernels --------------------------------------------
@pytest.mark.parametrize("tokens,n,c,dtype,tiles", [
    (8192, 4, 3584, "bfloat16", (256, 1792)),     # Xing4.0-29B-A4B.tokens
    (8192, 4, 3584, "float32", (256, 896)),       # 1,792 past the budget
    (300, 4, 256, "float32", (256, 256)),
    (64, 4, 512, "bfloat16", (128, 512)),         # fewer tokens than a block
    (4096, 2, 2048, "bfloat16", (256, 2048)),
    (4096, 8, 1280, "bfloat16", (256, 1280)),
    (4096, 8, 1280, "float32", (256, 640)),
    (64, 4, 32, "float32", None),                 # the rehearsal's width
    (8192, 4, 3500, "bfloat16", None),            # no multiple of 128
    (8192, 9, 3584, "bfloat16", None),            # more streams than 8
    (8192, 1, 3584, "bfloat16", None),            # one stream: no connection
    (8192, 4, 3584, "float16", None),
])
def test_mhc_tiles_table(tokens, n, c, dtype, tiles):
    for _ in range(2):                   # a pure function: the same answer
        assert mhc_kernels.mhc_tiles(tokens, n, c, jnp.dtype(dtype)) == tiles
    if tiles:
        assert mhc_kernels._vmem_bytes(*tiles, n, jnp.dtype(dtype).itemsize) \
            <= mhc_kernels.VMEM_BUDGET


def _hc_kernels(jaxpr):
    from test_kda_kernel import _kernels
    return [k for k in _kernels(jaxpr) if k.startswith("_mhc_")]


@pytest.mark.parametrize("width,tiled", [(C, True), (32, False)])
def test_a_refusal_leaves_the_spelling(width, tiled):
    """At a width the table takes, the connection's value and gradient trace
    the six kernels and no ``sinkhorn`` of the spelling; at one it refuses,
    none, and the spelling."""
    cfg = _cfg(d_model=width)
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    x = jax.random.normal(ks[0], (B, T, N * width))
    lp = {"hc_mix_" + kind: jax.ShapeDtypeStruct(shape, jnp.float32)
          for kind, shape in (("phi", (N * width, N * N + 2 * N)),
                              ("bias", (N * N + 2 * N,)), ("alpha", (3,)))}
    loss = lambda lp, x: jnp.sum(mhc.connect(lp, "hc_mix_", x, jnp.tanh,
                                             cfg))
    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, (0, 1)))(lp, x).jaxpr
    kernels = sorted(_hc_kernels(jaxpr))
    assert (mhc.kernel_tiles(cfg, B * T, jnp.float32) is not None) == tiled
    if tiled:
        assert kernels == sorted([
            "_mhc_coeffs_fwd_kernel", "_mhc_read_in_kernel",
            "_mhc_coeffs_bwd_kernel", "_mhc_in_bwd_kernel",
            "_mhc_out_fwd_kernel", "_mhc_out_bwd_kernel"])
    else:
        assert kernels == []


# -- the counter --------------------------------------------------------------
def _xing(hidden):
    """The Xing cell's layer table (one dense layer, four expert layers)
    at the rehearsal's sizes, ``hidden`` wide."""
    return HybridLMConfig(
        layer_types=("latent_attention",) * 5,
        ffn_types=("gated_mlp",) + ("sparse_experts",) * 4,
        d_model=hidden, d_ff=64, n_heads=4, n_kv_heads=4, q_lora_rank=16,
        kv_lora_rank=8, qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8,
        n_routed_experts=4, experts_per_token=2, moe_d_ff=16, vocab_size=96,
        seq_len=32, hc_mult=4, hc_sinkhorn_iters=3, tie_embeddings=False)


@pytest.mark.parametrize("kind,sublayers,kernels", [
    ("Xing", 10, 10), ("Ling", 0, 0), ("Granite", 0, 0)])
def test_hc_kernel_sublayers_counter(kind, sublayers, kernels):
    """The Xing table at a width that tiles: 10 of 10 sublayers on the
    kernels (at the rehearsal's width of 32, none:
    ``test_xing_lm.py::test_the_trace_notes_its_counters_and_scopes``).  A
    linear-attention layer as Ling has it and a Mamba-2 layer as Granite
    has it have one stream: no connection, no kernel."""
    from mxnet_tpu.parallel import MeshPlan
    from mxnet_tpu.telemetry import compiles
    from mxnet_tpu.transformer import HybridLM
    if kind == "Xing":
        cfg = _xing(128)
    elif kind == "Ling":
        cfg = HybridLMConfig(
            layer_types=("linear_attention",),
            d_model=64, d_ff=64, n_heads=4, n_kv_heads=4, kda_head_dim=16,
            kda_chunk=16, seq_len=32, vocab_size=64, tie_embeddings=False)
    else:
        cfg = HybridLMConfig(
            layer_types=("mamba",), d_model=32, d_ff=64,
            ssm_heads=4, ssm_head_dim=16, ssm_state=16, ssm_chunk=16,
            seq_len=32)
    program = HybridLM(cfg).mesh_program(MeshPlan(data=1))
    before = compiles.counters()
    x = jnp.zeros((2, 32), jnp.int32)
    jaxpr = jax.make_jaxpr(program.loss_replica)(
        tuple(jax.ShapeDtypeStruct(program._shapes[n], jnp.float32)
              for n in program.param_names), x, x, None).jaxpr
    after = compiles.counters()
    grew = {k: after[k] - before[k] for k in
            ("hyper_connection_sublayers", "hc_kernel_sublayers")}
    assert grew == {"hyper_connection_sublayers": sublayers,
                    "hc_kernel_sublayers": kernels}
    assert _hc_kernels(jaxpr).count("_mhc_coeffs_fwd_kernel") == kernels


# -- the declared costs -------------------------------------------------------
def test_each_kernels_cost_estimate_is_its_bytes():
    """One sublayer of the Xing cell (8,192 tokens, four streams of 3,584,
    bfloat16): each kernel's ``pl.CostEstimate`` moves one pass over its
    distinct operands and its results, as its declared cost reads and
    writes them, and as the family's count has it for the stream; the lint
    finds nothing undeclared."""
    from mxnet_tpu.analysis import lint_kernel_costs
    from mxnet_tpu.analysis.cost import KERNEL_COSTS, kernel_name_of
    from test_kda_kernel import _equations
    assert lint_kernel_costs() == []
    tokens, n, c, bf16 = 8192, 4, 3584, jnp.bfloat16
    tiles = mhc_kernels.mhc_tiles(tokens, n, c, bf16)
    sds = jax.ShapeDtypeStruct
    shapes = (sds((tokens, n * c), bf16), sds((tokens, c), bf16),
              sds((n * c, 24), bf16), sds((24,), bf16), sds((3,), bf16))

    def loss(x, y, phi, bias, alpha):
        u, h, x = mhc_kernels.connect_in(x, phi, bias, alpha, tiles,
                                         SETTINGS)
        out = mhc_kernels.connect_out(x, y + u, h, tiles)
        return jnp.sum(out.astype(jnp.float32))

    jaxpr = jax.make_jaxpr(jax.grad(loss, ALL))(*shapes).jaxpr
    calls = {kernel_name_of(e): e for e in _equations(jaxpr)
             if e.primitive.name == "pallas_call"}
    assert len(calls) == 6
    stream, narrow, rows = tokens * n * c * 2, tokens * c * 2, tokens * 512
    phi, ab = n * c * 128 * 2, 128 * 2 * 4
    moved = {
        "_mhc_coeffs_fwd_kernel": stream + phi + ab + 2 * rows,
        "_mhc_read_in_kernel": stream + rows + narrow,
        "_mhc_coeffs_bwd_kernel": stream + narrow + 2 * rows + ab
        + rows + ab,
        # phi's gradient is summed and written in float32
        # the stream passed through to the write-out: its cotangent is read
        "_mhc_in_bwd_kernel": 3 * stream + narrow + phi + 2 * rows
        + 2 * phi,
        "_mhc_out_fwd_kernel": 2 * stream + narrow + rows,
        "_mhc_out_bwd_kernel": 3 * stream + 2 * narrow + 2 * rows,
    }
    for name, eqn in calls.items():
        declared = KERNEL_COSTS[name](eqn)
        estimate = eqn.params["cost_estimate"]
        assert estimate.bytes_accessed == declared["bytes_read"] \
            + declared["bytes_written"] == moved[name], name
        assert declared["flops"] == estimate.flops > 0
