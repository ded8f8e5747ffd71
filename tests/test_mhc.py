"""Manifold-constrained hyper-connections (``transformer/mhc.py``) on the CPU
at small sizes: Sinkhorn's projection, the mixes against their explicit
sums, the single stream left as it was, and one connection in values and
gradients against the benchmark's plain reference
(``benchmark/families/mhc_mla_moe.py``, which imports nothing of
``mxnet_tpu``)."""
from __future__ import annotations

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.parallel import MeshPlan
from mxnet_tpu.transformer import HybridLM, HybridLMConfig, mhc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
EPS = 1e-6
# the last-normalised side (the rows) misses one by eps / (s + eps), s the
# row's sum before its division (about one), and by float32's rounding of
# four terms
ROWS_TOL = 1.5e-6


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


def _cfg(**kw):
    sizes = dict(d_model=16, hc_mult=4, hc_sinkhorn_iters=20, hc_eps=EPS,
                 norm_eps=1e-6)
    sizes.update(kw)
    return HybridLMConfig(layer_types=("latent_attention",), **sizes)


def _leaves(cfg, seed, scale=1.0):
    """One sublayer's connection leaves, drawn from ``seed``: ``alpha``
    ``scale`` times larger than the configuration's start, so that the
    coefficients move with the token."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    n, d = cfg.hc_mult, cfg.d_model
    width = n * n + 2 * n
    return {"hc_mix_phi": jax.random.normal(ks[0], (n * d, width))
            / np.sqrt(n * d),
            "hc_mix_bias": jax.random.uniform(ks[1], (width,), minval=-1,
                                              maxval=1),
            "hc_mix_alpha": jax.random.uniform(ks[2], (3,), minval=0.5,
                                               maxval=1.5) * scale}


def _stream(seed, cfg, b=2, t=5):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (b, t, cfg.hc_mult, cfg.d_model))


# -- Sinkhorn ----------------------------------------------------------------
@pytest.mark.parametrize("scale", [0.25, 0.5, 1.0])
def test_sinkhorn_rows_sum_to_one_and_columns_nearly(scale):
    """Logits of the spread the configuration starts from (biases within
    one): 20 iterations bring the columns within 1e-3 (at twice that spread
    they stop some 2% short)."""
    logits = jax.random.normal(jax.random.PRNGKey(7), (256, 4, 4)) * scale
    m = mhc.sinkhorn(jnp.exp(logits), 20, EPS)
    assert float(jnp.abs(m.sum(-1) - 1).max()) <= ROWS_TOL
    assert float(jnp.abs(m.sum(-2) - 1).max()) <= 1e-3
    assert float(m.min()) > 0


@pytest.mark.parametrize("beyond", [30.0, 1e4])
def test_sinkhorn_at_the_clamp_is_the_permutation_and_finite(beyond):
    """Logits at (or far past) the clamp of +-30, one large a row and
    column: H_res is the permutation within float32, both sides sum to one,
    and logits past the clamp read as the clamp's own."""
    cfg = _cfg()
    perm = np.stack([np.random.RandomState(s).permutation(4)
                     for s in range(64)])
    logits = np.full((64, 4, 4), -beyond, np.float32)
    np.put_along_axis(logits, perm[..., None], beyond, axis=-1)
    low, high = cfg.hc_res_clamp
    m = mhc.sinkhorn(jnp.exp(jnp.clip(jnp.asarray(logits), low, high)), 20,
                     EPS)
    assert bool(jnp.isfinite(m).all())
    assert float(jnp.abs(m.sum(-1) - 1).max()) <= ROWS_TOL
    assert float(jnp.abs(m.sum(-2) - 1).max()) <= 1e-3
    want = np.zeros((64, 4, 4), np.float32)
    np.put_along_axis(want, perm[..., None], 1.0, axis=-1)
    np.testing.assert_allclose(m, want, atol=1e-5)


def test_coefficients_are_the_documented_ranges():
    cfg = _cfg()
    h_pre, h_post, h_res = mhc.coefficients(
        *(_leaves(cfg, 1)["hc_mix_" + k] for k in ("phi", "bias", "alpha")),
        _stream(2, cfg), cfg)
    assert h_pre.dtype == h_post.dtype == h_res.dtype == jnp.float32
    assert h_pre.shape == (2, 5, 4) and h_res.shape == (2, 5, 4, 4)
    assert float(h_pre.min()) > 0 and float(h_pre.max()) < 1
    assert float(h_post.min()) > 0 and float(h_post.max()) < 2
    assert float(jnp.abs(h_res.sum(-1) - 1).max()) <= ROWS_TOL


# -- the mixes ---------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_read_in_and_write_out_are_the_explicit_sums(dtype):
    """The broadcast multiply-adds against the products written as
    ``einsum`` in float32 at ``highest``: float32 equal to rounding; in
    bfloat16 the sums are taken in float32 and rounded once, so they are
    the float32 result rounded."""
    cfg = _cfg()
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (2, 5, 4, 16)).astype(dtype)
    y = jax.random.normal(ks[1], (2, 5, 16)).astype(dtype)
    h_pre = jax.nn.sigmoid(jax.random.normal(ks[2], (2, 5, 4)))
    h_res = mhc.sinkhorn(jnp.exp(jax.random.normal(ks[3], (2, 5, 4, 4))),
                         cfg.hc_sinkhorn_iters, EPS)
    h_post = 2 * h_pre[..., ::-1]
    hi = jax.lax.Precision.HIGHEST
    xf, yf = x.astype(jnp.float32), y.astype(jnp.float32)
    want_u = jnp.einsum("btj,btjc->btc", h_pre, xf, precision=hi)
    want_x = jnp.einsum("btij,btjc->btic", h_res, xf, precision=hi) \
        + h_post[..., None] * yf[..., None, :]
    u = mhc.read_in(h_pre, x)
    out = mhc.write_out(h_res, h_post, x, y)
    assert u.dtype == out.dtype == dtype and out.shape == x.shape
    if dtype == jnp.float32:
        np.testing.assert_allclose(u, want_u, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(out, want_x, rtol=1e-6, atol=1e-6)
    else:
        np.testing.assert_array_equal(
            np.asarray(u, np.float32),
            np.asarray(want_u.astype(dtype), np.float32))
        close = np.isclose(np.asarray(out, np.float32),
                           np.asarray(want_x.astype(dtype), np.float32),
                           rtol=2 ** -8, atol=1e-6)
        assert close.all()


def test_expand_and_merge():
    """The streams side by side, (b, t, n C)."""
    e = jax.random.normal(jax.random.PRNGKey(4), (2, 3, 8))
    x = mhc.expand(e, 4)
    assert x.shape == (2, 3, 32)
    np.testing.assert_array_equal(x[..., 16:24], e)
    np.testing.assert_allclose(mhc.merge(x, 4), 4 * e, rtol=1e-6)


# -- one stream is today's residual ------------------------------------------
def test_hc_mult_1_is_the_single_residual_with_no_connection_leaves():
    """Without hyper-connections a layer is ``h + m`` with no leaf of a
    connection, and the program names no ``hyper_connection`` scope."""
    cfg = HybridLMConfig(layer_types=("attention",), d_model=16, d_ff=32,
                         n_heads=2, n_kv_heads=2, head_dim=8, seq_len=8,
                         vocab_size=24, residual_multiplier=0.5)
    assert cfg.hc_mult == 1
    program = HybridLM(cfg).mesh_program(MeshPlan(data=1))
    assert not [n for n in program.param_names if "hc_" in n]
    params = program.init_params(0)
    lp = {n[3:]: v for n, v in params.items() if n.startswith("l0_")}
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 8, 16))
    m = program._mixer("attention", lp, h)
    np.testing.assert_array_equal(program._mix("attention", lp, h),
                                  h + 0.5 * m)
    x = jnp.zeros((2, 8), jnp.int32)
    text = jax.jit(lambda v: program.loss_replica(v, x, x, None)).lower(
        tuple(params[n] for n in program.param_names)).as_text(
            debug_info=True)
    assert "hyper_connection" not in text


def test_several_streams_refuse_a_prediction_module():
    with pytest.raises(ValueError, match="hc_mult"):
        HybridLMConfig(hc_mult=4, mtp_modules=1)
    with pytest.raises(ValueError, match="hc_mult"):
        HybridLMConfig(hc_mult=0)


# -- one connection against the reference ------------------------------------
def test_a_connection_is_the_references_in_values_and_gradients(ref):
    """``mhc.connect`` around a small sublayer against the family's
    ``hyper_connection`` (the equations as written, Sinkhorn as a plain
    loop, the mixes as sums over ``j``) in float32 at ``highest``: values
    and the gradients of the stream and of every leaf within 2e-5 of the
    largest (the two differ in where the norm's factor is applied)."""
    cfg = _cfg(hc_sinkhorn_iters=5)
    lp = _leaves(cfg, 8, scale=1.0)
    w = jax.random.normal(jax.random.PRNGKey(9), (16, 16)) / 4
    x = _stream(10, cfg)
    plain = {"hc_mult": 4, "rms_norm_eps": 1e-6, "hc_sinkhorn_iters": 5,
             "hc_eps": EPS, "mhc_h_res_clamp_min": -30,
             "mhc_h_res_clamp_max": 30}

    def sublayer(u):
        return jnp.tanh(u @ w)

    def ours(lp, x):
        return jnp.sum(jnp.sin(mhc.connect(lp, "hc_mix_", x, sublayer, cfg)))

    def theirs(lp, x):
        return jnp.sum(jnp.sin(ref.hyper_connection(
            plain, lp, "hc_mix_", x, sublayer, ref.HOLD["float32"])))

    with jax.default_matmul_precision("highest"):
        a, (ga_lp, ga_x) = jax.value_and_grad(ours, (0, 1))(lp, x)
        b, (gb_lp, gb_x) = jax.value_and_grad(theirs, (0, 1))(lp, x)
        out_a = mhc.connect(lp, "hc_mix_", x, sublayer, cfg)
        out_b = ref.hyper_connection(plain, lp, "hc_mix_", x, sublayer,
                                     ref.HOLD["float32"])
    np.testing.assert_allclose(out_a, out_b, rtol=2e-5, atol=2e-5)
    assert float(a) == pytest.approx(float(b), rel=1e-5)
    for name in lp:
        scale = float(jnp.abs(gb_lp[name]).max())
        assert float(jnp.abs(ga_lp[name] - gb_lp[name]).max()) <= \
            2e-5 * scale, name
    np.testing.assert_allclose(ga_x, gb_x, rtol=2e-5,
                               atol=2e-5 * float(jnp.abs(gb_x).max()))
