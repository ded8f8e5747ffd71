"""Manifold-constrained hyper-connections (mHC: "Manifold-Constrained
Hyper-Connections", DeepSeek-AI, after Hyper-Connections, Zhu et al.,
arXiv:2409.19606) as the residual path of :class:`~.hybrid.HybridLM`'s
layer table (docs/transformer.md "The layer table").

The residual stream is ``n = hc_mult`` streams of the model's width ``C``,
held ``(b, t, n C)`` between the layers (the streams side by side, ``C``
the minor axis; :func:`coefficients`, :func:`read_in` and :func:`write_out`
read the view ``(b, t, n, C)``).  Around every sublayer ``F``
(a layer's mixer, then its feed-forward), with coefficients a token and in
float32 from the token's own streams::

    v      = RMSNorm(vec(x))                     over the n C values, no gain
    h_pre  = a_pre  (v phi_pre)  + b_pre         (n)
    h_post = a_post (v phi_post) + b_post        (n)
    G_res  = a_res  mat(v phi_res) + b_res       (n, n)
    H_pre  = sigmoid(h_pre) ;  H_post = 2 sigmoid(h_post)
    H_res  = Sinkhorn(exp(clamp(G_res)))         columns then rows, each sum
                                                 plus hc_eps, hc_sinkhorn_iters
                                                 times
    u      = sum_j H_pre[j] x_j   -> F's own norm -> F -> y
    x'_i   = sum_j H_res[i, j] x_j + H_post[i] y

The streams start as ``n`` copies of the embedding (:func:`expand`) and are
summed before the final norm (:func:`merge`).  A sublayer's leaves are
``phi`` (n C, n^2 + 2n: the columns of ``pre``, ``post`` and ``res``),
``bias`` (n^2 + 2n, the same order) and ``alpha`` (3: ``a_pre``, ``a_post``,
``a_res``).

The mixes are ``n`` broadcast multiply-adds over the stream, summed in
float32 and rounded to the stream's type once: as products of ``n x n``
matrices a token they would go to the matrix unit padded to its tile.

**Which path runs.**  :func:`connect` asks :func:`kernel_tiles`
(``ops/mhc_kernels.mhc_tiles`` over the tokens, ``n``, ``C`` and the
stream's dtype) and nothing else: where it gives blocks (``C`` a multiple of
128, ``n`` from 2 to 8) the connection is that module's two kernel pairs,
``connect_in`` (the coefficients and the read-in) and ``connect_out`` (the
write-out), the same mathematics at the same precision; everywhere else it
is :func:`coefficients`, :func:`read_in` and :func:`write_out`, which the
tests hold the kernels to.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import mhc_kernels

__all__ = ["leaves", "coefficients", "sinkhorn", "read_in", "write_out",
           "expand", "merge", "connect", "kernel_tiles", "SUBLAYERS"]

# the two sublayers of a layer, by the prefix of their leaves' kinds
SUBLAYERS = ("hc_mix_", "hc_ffn_")


def leaves(cfg):
    """[(kind, shape)] of one layer's connection leaves: both sublayers'."""
    n, d = cfg.hc_mult, cfg.d_model
    width = n * n + 2 * n
    return [(prefix + kind, shape) for prefix in SUBLAYERS
            for kind, shape in (("phi", (n * d, width)), ("bias", (width,)),
                                ("alpha", (3,)))]


def sinkhorn(m, iters, eps):
    """``iters`` times the columns, then the rows, of the positive ``m``
    (..., n, n) divided by their sums plus ``eps``: the rows sum to one
    within ``eps``, the columns as near as the iterations bring them."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
    return m


def coefficients(phi, bias, alpha, x, cfg):
    """``(H_pre, H_post, H_res)`` float32 (b, t, n), (b, t, n), (b, t, n, n)
    of the stream ``x`` (b, t, n, C).  The norm has no gain and its factor is
    one scalar a token, so it multiplies the product with ``phi`` (which
    reads the stream as it is held) rather than the stream."""
    f32 = jnp.float32
    n = cfg.hc_mult
    b, t = x.shape[:2]
    flat = x.reshape(b, t, -1)
    mean_square = jnp.mean(jnp.square(flat.astype(f32)), axis=-1,
                           keepdims=True)
    proj = jnp.einsum("btk,kc->btc", flat, phi,
                      preferred_element_type=f32) \
        * lax.rsqrt(mean_square + cfg.norm_eps)
    alpha, bias = alpha.astype(f32), bias.astype(f32)
    h_pre = alpha[0] * proj[..., :n] + bias[:n]
    h_post = alpha[1] * proj[..., n:2 * n] + bias[n:2 * n]
    g_res = alpha[2] * proj[..., 2 * n:] + bias[2 * n:]
    low, high = cfg.hc_res_clamp
    h_res = sinkhorn(jnp.exp(jnp.clip(g_res, low, high)).reshape(b, t, n, n),
                     cfg.hc_sinkhorn_iters, cfg.hc_eps)
    return jax.nn.sigmoid(h_pre), 2.0 * jax.nn.sigmoid(h_post), h_res


def read_in(h_pre, x):
    """``u = sum_j H_pre[j] x_j`` (b, t, C) of ``H_pre`` (b, t, n) and the
    stream ``x`` (b, t, n, C): summed in float32, rounded once to ``x``'s
    type."""
    terms = [h_pre[..., j, None] * x[..., j, :].astype(jnp.float32)
             for j in range(x.shape[-2])]
    u = terms[0]
    for term in terms[1:]:
        u = u + term
    return u.astype(x.dtype)


def write_out(h_res, h_post, x, y):
    """``x'_i = sum_j H_res[i, j] x_j + H_post[i] y``, the stream after a
    sublayer whose result is ``y`` (b, t, C): each stream summed in float32,
    rounded once to ``x``'s type."""
    f32 = jnp.float32
    n = x.shape[-2]
    out = []
    for i in range(n):
        s = h_post[..., i, None] * y.astype(f32)
        for j in range(n):
            s = s + h_res[..., i, j, None] * x[..., j, :].astype(f32)
        out.append(s)
    return jnp.stack(out, axis=-2).astype(x.dtype)


def expand(e, n):
    """The streams at the start: ``n`` copies of the embedding ``e`` side
    by side, (b, t, C) -> (b, t, n C)."""
    return jnp.concatenate([e] * n, axis=-1)


def merge(x, n):
    """The ``n`` streams at the end, summed: (b, t, n C) -> (b, t, C), in
    float32 and rounded once."""
    c = x.shape[-1] // n
    return functools.reduce(jnp.add, [x[..., j * c:(j + 1) * c].astype(
        jnp.float32) for j in range(n)]).astype(x.dtype)


def kernel_tiles(cfg, tokens, dtype):
    """The blocks of ``ops/mhc_kernels.py`` for a connection of ``tokens``
    tokens of the stream in ``dtype``, or None where the spelling runs."""
    return mhc_kernels.mhc_tiles(tokens, cfg.hc_mult, cfg.d_model, dtype)


def connect(lp, prefix, x, sublayer, cfg):
    """The stream ``x`` (b, t, n C), the ``n`` streams side by side (or its
    view (b, t, n, C); the result has ``x``'s shape), after ``sublayer``
    (the sublayer's own norm and body, (b, t, C) -> (b, t, C)) wrapped in
    its hyper-connection, whose leaves are ``lp[prefix + ...]``.  The
    connection's work is under the scope ``hyper_connection``:
    ``hc_coeffs``, ``hc_read_in``, ``hc_write_out``; the sublayer's under
    its own."""
    (b, t), n, c = x.shape[:2], cfg.hc_mult, cfg.d_model
    wide = n * c
    tiles = kernel_tiles(cfg, b * t, x.dtype)
    if tiles is not None:
        with jax.named_scope("hyper_connection"):
            flat = mhc_kernels.whole_blocks(x.reshape(b * t, wide), tiles)
            u, h, flat = mhc_kernels.connect_in(
                flat, lp[prefix + "phi"], lp[prefix + "bias"],
                lp[prefix + "alpha"], tiles,
                (cfg.norm_eps, tuple(cfg.hc_res_clamp),
                 cfg.hc_sinkhorn_iters, cfg.hc_eps))
        y = sublayer(u[:b * t].reshape(b, t, c))
        with jax.named_scope("hyper_connection"), \
                jax.named_scope("hc_write_out"):
            y = mhc_kernels.whole_blocks(y.reshape(b * t, c), tiles)
            out = mhc_kernels.connect_out(flat, y, h, tiles)
            return out[:b * t].reshape(x.shape)
    shape, x = x.shape, x.reshape(b, t, n, c)
    with jax.named_scope("hyper_connection"):
        with jax.named_scope("hc_coeffs"):
            h_pre, h_post, h_res = coefficients(
                lp[prefix + "phi"], lp[prefix + "bias"], lp[prefix + "alpha"],
                x, cfg)
        with jax.named_scope("hc_read_in"):
            u = read_in(h_pre, x)
    y = sublayer(u)
    with jax.named_scope("hyper_connection"), \
            jax.named_scope("hc_write_out"):
        return write_out(h_res, h_post, x, y).reshape(shape)
