"""Mamba-2 (state-space duality, Dao & Gu, arXiv:2405.21060) as a mixer of
``transformer/hybrid.py``: the chunked scan whose backward the training
step runs.  :func:`ssd_chunked` has two spellings of the same terms, chosen
while it is traced from the scan's shapes (``ops/ssd_kernels.py::tiles``, a
pure function of chunk, heads, head width, state and dtype): where they
tile, a Pallas kernel pair behind a ``jax.custom_vjp``, forward and
hand-written backward, which keeps every ``L x L`` array and the carried
state in VMEM; elsewhere plain ``jax.numpy``/``lax`` einsums and one scan
step a chunk, whose backward autodiff gives.  ``dt``'s softplus, ``A`` and
the cumulative sums are ``jax.numpy`` in both.  The casts are at the same
places.

One layer (docs/transformer.md "The layer table" has the leaves)::

    [z, xBC, dt] = x W_in                     (d -> 2 d_inner + 2 N + H)
    xBC = silu(causal_depthwise_conv1d(xBC))  (width K, with bias)
    x, B, C = split(xBC)                      x: H heads of P; B, C: N, shared
    dt = softplus(dt + dt_bias);  A = -exp(A_log)      one scalar a head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T         (P x N a head, S_0 = 0)
    y_t = S_t C_t + D x_t
    out = (RMSNorm(y * silu(z)) * w) W_out

:func:`ssd_chunked` computes the recurrence chunk by chunk: inside a chunk
of ``L`` steps the quadratic form ``(decay o C B^T)(dt x)``, between chunks
the carried state.  :func:`ssd_recurrence` (the defining scan over time)
and :func:`ssd_quadratic` (one chunk as long as the sequence) are the two
other spellings of the same map; the tests hold all three together, in
values and in gradients.

What is float32 whatever the compute dtype: ``dt`` after the softplus, the
log-decays ``dt A``, their cumulative sums and the exponentials of their
differences, the carried state, and the accumulation of every product.
The operands of the products (``x``, ``B``, ``C``, the masked decay matrix)
stay in the compute dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..ops import ssd_kernels

__all__ = ["ssd_chunked", "ssd_recurrence", "ssd_quadratic",
           "scan_kernel_tiles", "causal_conv1d", "mamba2_mixer",
           "PROJECTION"]

# the ``checkpoint_name`` of a projection product's result: what a layer's
# ``jax.checkpoint`` may keep for the backward pass (``hybrid.py``)
PROJECTION = "projection"


def _log_decay(dt, a_log):
    """``dt_t A`` in float32, ``A = -exp(A_log)``: the log of the factor
    by which step ``t`` shrinks the state.  (b, t, h)."""
    return dt.astype(jnp.float32) * -jnp.exp(a_log.astype(jnp.float32))


def ssd_recurrence(x, dt, a_log, B, C):
    """The defining recurrence, one ``lax.scan`` step a token.
    x (b, t, h, p); dt (b, t, h), already positive; a_log (h,);
    B, C (b, t, n).  Returns y (b, t, h, p) without the ``D x`` term."""
    la = _log_decay(dt, a_log)
    xdt = x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None]

    def step(state, inp):
        la_t, xdt_t, b_t, c_t = inp
        state = (jnp.exp(la_t)[..., None, None] * state
                 + xdt_t[..., None] * b_t[:, None, None, :])
        return state, jnp.einsum("bhpn,bn->bhp", state, c_t)

    b, _, h, p = x.shape
    state0 = jnp.zeros((b, h, p, B.shape[-1]), jnp.float32)
    seq = tuple(jnp.moveaxis(v, 1, 0) for v in
                (la, xdt, B.astype(jnp.float32), C.astype(jnp.float32)))
    _, y = lax.scan(step, state0, seq)
    return jnp.moveaxis(y, 0, 1).astype(x.dtype)


def _decay_matrix(cum):
    """``exp(cum_i - cum_j)`` for ``i >= j``, 0 above the diagonal.
    cum (..., L, h) -> (..., h, L, L), float32.  The difference is masked
    before the exponential: above the diagonal it is positive and may
    overflow."""
    cum = jnp.moveaxis(cum, -1, -2)                       # (..., h, L)
    diff = cum[..., :, None] - cum[..., None, :]
    size = cum.shape[-1]
    lower = jnp.tril(jnp.ones((size, size), bool))
    return jnp.exp(jnp.where(lower, diff, -jnp.inf))


def scan_kernel_tiles(cfg, dtype):
    """Whether :func:`ssd_chunked` spells a scan of ``cfg``'s sizes in
    ``dtype`` as the kernel pair."""
    return ssd_kernels.tiles(cfg.ssm_chunk, cfg.ssm_heads, cfg.ssm_head_dim,
                             cfg.ssm_state, dtype)


def ssd_chunked(x, dt, a_log, B, C, chunk):
    """The same map as :func:`ssd_recurrence`, ``chunk`` steps at a time.
    A sequence that is no multiple of ``chunk`` is padded at its end with
    steps of ``dt = 0`` (no decay, no input), which no earlier step sees."""
    b, t, h, p = x.shape
    n = B.shape[-1]
    pad = -t % chunk
    if pad:
        x, dt, B, C = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
                       for v in (x, dt, B, C))
    c = (t + pad) // chunk
    dtype = x.dtype
    f32 = jnp.float32
    la = _log_decay(dt, a_log).reshape(b, c, chunk, h)
    cum = jnp.cumsum(la, axis=2)                          # (b, c, L, h)
    Bc = B.reshape(b, c, chunk, n)
    Cc = C.reshape(b, c, chunk, n)
    if ssd_kernels.tiles(chunk, h, p, n, dtype):
        y = ssd_kernels.ssd_scan(Cc, Bc, x.reshape(b, c, chunk, h, p),
                                 dt.astype(f32).reshape(b, c, chunk, h), cum)
        return y.reshape(b, c * chunk, h, p)[:, :t]
    xdt = (x.astype(f32) * dt.astype(f32)[..., None]).astype(dtype)
    xdt = xdt.reshape(b, c, chunk, h, p)

    # inside a chunk: y_i += sum_{j<=i} exp(cum_i - cum_j) (C_i . B_j) xdt_j
    scores = jnp.einsum("bcln,bcsn->bcls", Cc, Bc, preferred_element_type=f32)
    mixed = (scores[:, :, None] * _decay_matrix(cum)).astype(dtype)
    y = jnp.einsum("bchls,bcshp->bclhp", mixed, xdt,
                   preferred_element_type=f32)

    # what a chunk adds to the state by its end, and what it keeps of the
    # state it was handed
    to_end = jnp.exp(cum[:, :, -1:, :] - cum)             # (b, c, L, h)
    added = jnp.einsum("bcshp,bcsn->bchpn",
                       (xdt.astype(f32) * to_end[..., None]).astype(dtype),
                       Bc, preferred_element_type=f32)
    kept = jnp.exp(cum[:, :, -1, :])                      # (b, c, h)

    def carry(state, inp):
        kept_c, added_c = inp
        return kept_c[..., None, None] * state + added_c, state

    _, entering = lax.scan(
        carry, jnp.zeros((b, h, p, n), f32),
        (jnp.moveaxis(kept, 1, 0), jnp.moveaxis(added, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)               # (b, c, h, p, n)

    # the state a chunk was handed, read by every step of the chunk
    y = y + jnp.einsum("bcln,bchpn->bclhp", Cc, entering.astype(dtype),
                       preferred_element_type=f32) \
        * jnp.exp(cum)[..., None]
    return y.reshape(b, c * chunk, h, p)[:, :t].astype(dtype)


def ssd_quadratic(x, dt, a_log, B, C):
    """The whole sequence as one quadratic form ``(L o C B^T)(dt x)``,
    ``L_ij = exp(sum_{j<k<=i} dt_k A)``: the attention-like dual."""
    f32 = jnp.float32
    cum = jnp.cumsum(_log_decay(dt, a_log), axis=1)       # (b, t, h)
    scores = jnp.einsum("bln,bsn->bls", C.astype(f32), B.astype(f32))
    mixed = scores[:, None] * _decay_matrix(cum)
    xdt = x.astype(f32) * dt.astype(f32)[..., None]
    return jnp.einsum("bhls,bshp->blhp", mixed, xdt).astype(x.dtype)


def causal_conv1d(x, weight, bias):
    """Depthwise causal convolution over time: ``y_t = bias + sum_k
    weight[k] x_{t-(K-1)+k}``, zeros before the sequence.  x (b, t, c);
    weight (K, c); bias (c,).  Spelled as K shifted products: K is 4."""
    width = weight.shape[0]
    t = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = bias.astype(x.dtype)
    for k in range(width):
        out = out + padded[:, k:k + t] * weight[k].astype(x.dtype)
    return out


def _gated_rms_norm(y, z, weight, eps):
    """``RMSNorm(y * silu(z)) * weight`` over the whole inner width, the
    mean of squares in float32."""
    g = (y * jax.nn.silu(z)).astype(jnp.float32)
    g = g * lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + eps)
    return (g * weight.astype(jnp.float32)).astype(y.dtype)


def mamba2_mixer(lp, x, cfg):
    """One Mamba-2 mixer over the normed residual ``x`` (b, t, d).  ``lp``
    holds the layer's leaves by kind; ``cfg`` gives ``ssm_heads``,
    ``ssm_head_dim``, ``ssm_state``, ``ssm_chunk`` and ``norm_eps``."""
    h, p, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    inner = h * p
    b, t, _ = x.shape
    with jax.named_scope("ssm_in_proj"):
        proj = checkpoint_name(x @ lp["ssm_in"], PROJECTION)
        z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * n], axis=-1)
    with jax.named_scope("ssm_conv"):
        xbc = jax.nn.silu(causal_conv1d(xbc, lp["ssm_conv_w"],
                                        lp["ssm_conv_b"]))
        xs, B, C = jnp.split(xbc, [inner, inner + n], axis=-1)
    with jax.named_scope("ssm_scan"):
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + lp["ssm_dt_bias"].astype(jnp.float32))
        xs = xs.reshape(b, t, h, p)
        y = ssd_chunked(xs, dt, lp["ssm_a_log"], B, C, cfg.ssm_chunk)
        y = y + xs * lp["ssm_d"].astype(xs.dtype)[:, None]
    with jax.named_scope("ssm_gate_norm"):
        y = _gated_rms_norm(y.reshape(b, t, inner), z, lp["ssm_norm"],
                            cfg.norm_eps)
    with jax.named_scope("ssm_out_proj"):
        return checkpoint_name(y @ lp["ssm_out"], PROJECTION)
