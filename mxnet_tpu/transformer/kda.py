"""Kimi Delta Attention (Kimi Linear, arXiv:2510.26692): a gated delta rule
with a decay per head **and channel**, as the ``linear_attention`` mixer of
:class:`~.hybrid.HybridLM`'s layer table (docs/transformer.md "The layer
table").  It stands beside ``ssm.py``: the short causal convolution is
``ssm.causal_conv1d``, and the state is carried chunk by chunk as
``ssm.ssd_chunked`` carries its own.

One layer over the normed residual ``x`` (``H`` heads of ``E`` key and value
columns)::

    q, k, v = SiLU(conv(x W_q)), SiLU(conv(x W_k)), SiLU(conv(x W_v))
                                    causal depthwise, width K, no bias
    q, k    = q / |q|_2, k / |k|_2  a head (eps 1e-6);  q = q * E^-0.5
    g_t     = lower_bound * sigmoid(exp(A_log_h) * (x W_f + dt_bias))
                                    a head AND channel, in (lower_bound, 0)
    beta_t  = sigmoid(x W_b)        a head
    S_t     = Diag(exp(g_t)) S_{t-1}
    S_t     = S_t + beta_t k_t (v_t - S_t^T k_t)^T      S (E x E), S_0 = 0
    o_t     = S_t^T q_t
    out     = (RMSNorm(o) * sigmoid(x W_g)[head]) W_o   the norm over all
                                    H x E columns, the gate a scalar a head

:func:`kda_recurrence` is that recurrence, one ``lax.scan`` step a token: the
definition the tests hold :func:`kda_chunked` to, in values and gradients.

**Chunk by chunk** (:func:`kda_chunked`, what the step runs).  With ``G_i``
the running sum of ``g`` inside a chunk of ``L`` tokens and ``S_0`` the state
the chunk is handed, the rule's corrections ``u_i = beta_i (v_i - S_i'^T
k_i)`` solve a unit lower triangular system::

    (I + A) U = beta (V - (K e^G) S_0),   A_ij = beta_i <k_i e^{G_i}, k_j e^{-G_j}>, j < i
    O = (Q e^G) S_0 + tril(<q_i e^{G_i}, k_j e^{-G_j}>) U
    S_L = Diag(e^{G_L}) S_0 + (K e^{G_L - G})^T U

``(I + A)^-1`` is computed in float32 at the highest precision by forward
substitution in blocks of 16 rows and from halves above that
(:func:`_unit_lower_inverse`; the nilpotent ``A``'s product ``(I - A)(I +
A^2)(I + A^4)...`` is exact on paper and loses every digit in float32 once a
chunk's keys align).  Across chunks one ``lax.scan`` carries ``S``.

**The exponent.**  A chunk of 64 may decay by ``e^-320``, so ``e^{-G_j}``
alone overflows float32.  As the public ``chunk_kda`` kernel does, exponents
are kept relative to sub-blocks of :data:`SUB_BLOCK` tokens: a row of
sub-block ``a`` carries ``e^{G_i - r_a}`` and a column ``j`` seen from it
``e^{r_a - G_j}``, with ``r_a`` the running sum in the middle of the
sub-block: within ``e^+-40`` for a token of the sub-block itself at the
gate's bound of -5 (8 tokens either way), at most 1 for a column of an
earlier one.  That is why the gate is bounded.  (With ``r_a`` where the
sub-block starts the factors reach ``e^+-80``: they fit, but the cotangents
of the small ones fall under float32's least normal number and the gradient
of ``k`` at the bound is off by 5e-4; in the middle it is exact to rounding.)

**Which path runs.**  :func:`kda_mixer` asks :func:`scan_kernel_tiles`
(``ops/kda_kernels.tiles`` over the chunk, the heads, their width and the
compute dtype) and nothing else: the shapes it takes (the ``Ling-3.0-flash``
cell's chunks of 64 and heads of 128 columns) run the same algebra as the
Pallas kernel pair ``kda_kernels.kda_scan``, every array below in VMEM only;
every other shape runs :func:`kda_chunked`, which is also what the tests
hold the kernels to.  The next paragraph describes :func:`kda_chunked`, the
``jax.numpy`` path, alone.

**Memory.**  The chunks' arrays (three decayed copies of ``k``, two of ``q``,
the ``L x L`` matrices and the factors of the inverse, ``W``, ``U`` and the
state every chunk is handed) would be some 5 GB a layer at 16k tokens of 32
heads if autodiff kept them; the sequence is therefore taken
:data:`SCAN_BLOCK_CHUNKS` chunks at a time under a ``jax.checkpoint``, whose
backward pass keeps one carried state a block and re-runs the block (some
0.6 GB at 32 chunks of 64).

What is float32 whatever the compute dtype: the gate, its running sums and
every exponential of them, ``beta``, the system ``A`` and its inverse, the
carried state and the accumulation of every product.  The operands of the
products are of the compute dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..ops import kda_kernels
from .layers import rms_norm
from .ssm import PROJECTION, causal_conv1d

__all__ = ["SUB_BLOCK", "SCAN_BLOCK_CHUNKS", "leaves", "product_widths",
           "kda_recurrence", "kda_chunked", "scan_kernel_tiles", "kda_mixer"]

# tokens whose exponents share a reference, the running sum in their middle
SUB_BLOCK = 16
# chunks one checkpointed block of the sequence holds in ``kda_chunked``, the
# ``jax.numpy`` path (the kernels of ``ops/kda_kernels.py`` have no block
# and do not read this).  A block costs some
# 760 device operations around its chunks' scan whatever its size, and the
# profiler's buffer holds 3.73 million: at 16 the cell's step ran 124,000 and
# a traced run kept 30 of its 40 steps; at 32 it runs 99,000 and 37.7 are
# kept, but the scan takes 555 ms a step for 457 (the blocks' arrays double);
# at 64 the compiler unrolls the four blocks (PERF.md section 6, PR 38)
SCAN_BLOCK_CHUNKS = 32
# the most a sub-block may decay by, in all: half of it either way of the
# reference keeps every factor and its cotangent well inside float32
MAX_EXPONENT = 80.0


def leaves(cfg):
    """[(kind, shape)] of the mixer's leaves, in declaration order."""
    d, h, k = cfg.d_model, cfg.n_heads, cfg.kda_conv
    inner = h * cfg.kda_head_dim
    return [("kda_wq", (d, inner)), ("kda_wk", (d, inner)),
            ("kda_wv", (d, inner)), ("kda_wf", (d, inner)),
            ("kda_wb", (d, h)), ("kda_wg", (d, h)),
            ("kda_conv_q", (k, inner)), ("kda_conv_k", (k, inner)),
            ("kda_conv_v", (k, inner)), ("kda_a_log", (h,)),
            ("kda_dt_bias", (inner,)), ("kda_norm", (inner,)),
            ("kda_wo", (inner, d))]


def product_widths(cfg):
    """Widths of the mixer's tagged projection products."""
    inner = cfg.n_heads * cfg.kda_head_dim
    return [inner] * 4 + [cfg.d_model]


def kda_recurrence(q, k, v, g, beta):
    """The defining recurrence, one ``lax.scan`` step a token, in float32.
    q, k, v (b, t, h, e), ``q`` already scaled; g (b, t, h, e), the log of
    the decay, at most 0; beta (b, t, h).  Returns o (b, t, h, e)."""
    f32 = jnp.float32

    def step(state, inp):
        q_t, k_t, v_t, g_t, beta_t = inp
        state = jnp.exp(g_t)[..., None] * state
        seen = jnp.einsum("bhdp,bhd->bhp", state, k_t)
        state = state + jnp.einsum(
            "bhd,bhp->bhdp", k_t, beta_t[..., None] * (v_t - seen))
        return state, jnp.einsum("bhdp,bhd->bhp", state, q_t)

    b, _, h, e = q.shape
    seq = tuple(jnp.moveaxis(a.astype(f32), 1, 0)
                for a in (q, k, v, g, beta))
    _, o = lax.scan(step, jnp.zeros((b, h, e, v.shape[-1]), f32), seq)
    return jnp.moveaxis(o, 0, 1).astype(q.dtype)


def _times(x, y):
    return jnp.einsum("...ij,...jk->...ik", x, y,
                      precision=lax.Precision.HIGHEST)


def _inverse(a):
    """``(I + a)^-1`` of strictly lower triangular ``a`` (..., n, n): up to
    :data:`SUB_BLOCK` rows by forward substitution, row ``i`` from the rows
    above it; larger ones from their halves, ``[[T1, 0], [-T2 a21 T1,
    T2]]``, equal halves as one batch of two (a chunk of 64 is one
    substitution over its four diagonal blocks: unrolled four times over,
    these rows were a third of the step's instructions and most of its
    770 MB of code).  Every quantity on the way is one of the result's own
    blocks, which for the delta rule's system are bounded by 1."""
    size = a.shape[-1]
    if size > SUB_BLOCK:
        half = size // 2
        if size % 2:
            first = _inverse(a[..., :half, :half])
            second = _inverse(a[..., half:, half:])
        else:
            first, second = _inverse(jnp.stack(
                [a[..., :half, :half], a[..., half:, half:]]))
        below = -_times(_times(second, a[..., half:, :half]), first)
        return jnp.concatenate(
            [jnp.concatenate([first, jnp.zeros_like(a[..., :half, half:])],
                             axis=-1),
             jnp.concatenate([below, second], axis=-1)], axis=-2)
    eye = jnp.eye(size, dtype=a.dtype)
    rows = [jnp.broadcast_to(eye[0], a.shape[:-2] + (size,))]
    for i in range(1, size):
        above = jnp.stack(rows, axis=-2)                  # (..., i, n)
        rows.append(eye[i] - jnp.einsum(
            "...j,...jk->...k", a[..., i, :i], above,
            precision=lax.Precision.HIGHEST))
    return jnp.stack(rows, axis=-2)


@jax.custom_vjp
def _unit_lower_inverse(a):
    """``(I + a)^-1`` of strictly lower triangular ``a`` (..., L, L),
    float32 (:func:`_inverse`).  Not the product ``(I - a)(I + a^2)(I +
    a^4)...``, exact as it is on paper: the powers of ``a`` reach
    ``C(63, k) |a|^k`` and cancel, and once a chunk's keys point the same
    way (``<k_i, k_j>`` near 1, as after a few steps of training) float32
    loses every digit of the result (errors of 1e3 to 1e21 against a
    result under 1: the first tree of PR 38 trained to NaN within ten
    steps on the chip)."""
    return _inverse(a)


def _unit_lower_inverse_fwd(a):
    inverse = _inverse(a)
    return inverse, inverse


def _unit_lower_inverse_bwd(inverse, g):
    # d(I + a)^-1 = -T da T
    transposed = jnp.swapaxes(inverse, -1, -2)
    return (-_times(_times(transposed, g), transposed),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _block(state, xs, sub):
    """``per_block`` chunks of the sequence from the state ``state``
    (b, h, e, p) float32: ``(state after them, their outputs)``.  ``xs``:
    q, k, v, g (b, c, L, h, e) and beta (b, c, L, h).  Inside, heads come
    before a chunk's tokens, so that every product is a batch of plain
    matrix products."""
    q, k, v, g = (jnp.moveaxis(a, 3, 2) for a in xs[:4])  # (b,c,h,L,e)
    beta = jnp.moveaxis(xs[4], 3, 2)[..., None]           # (b,c,h,L,1)
    b, c, h, size, e = q.shape
    dtype, f32 = q.dtype, jnp.float32
    n_sub = size // sub
    total = jnp.cumsum(g, axis=3)                         # G
    # r_a: G in the middle of sub-block a
    ref = total[:, :, :, max(sub // 2 - 1, 0)::sub]       # (b,c,h,a,e)
    down = jnp.exp(total - jnp.repeat(ref, sub, axis=3))  # e^{G_i - r_a(i)}
    # e^{r_a - G_j} for the columns j a row of sub-block a sees: those of
    # earlier sub-blocks (at most 1) and of its own (within e^+-40)
    seen = (jnp.arange(size) // sub)[None, :] <= jnp.arange(n_sub)[:, None]
    up = jnp.exp(jnp.where(seen[:, :, None],
                           ref[:, :, :, :, None] - total[:, :, :, None],
                           -jnp.inf))                     # (b,c,h,a,L,e)
    k_cols = (k[:, :, :, None] * up).astype(dtype)

    def against_columns(rows):
        rows = (rows * down).astype(dtype).reshape(b, c, h, n_sub, sub, e)
        return jnp.einsum("bchaid,bchajd->bchaij", rows, k_cols,
                          preferred_element_type=f32
                          ).reshape(b, c, h, size, size)

    below = jnp.tril(jnp.ones((size, size), bool), -1)
    at_or_below = jnp.tril(jnp.ones((size, size), bool))
    system = jnp.where(below, against_columns(k), 0.0) * beta
    scores = jnp.where(at_or_below, against_columns(q), 0.0).astype(dtype)
    inverse = _unit_lower_inverse(system).astype(dtype)
    decay = jnp.exp(total)                                # e^{G_i}
    w = jnp.einsum("bchls,bchsd->bchld", inverse,
                   (k * decay * beta).astype(dtype),
                   preferred_element_type=f32)
    u_own = jnp.einsum("bchls,bchsp->bchlp", inverse,
                       (v * beta).astype(dtype), preferred_element_type=f32)
    q_dec = (q * decay).astype(dtype)
    last = total[:, :, :, -1]                             # G_L, (b,c,h,e)
    k_end = (k * jnp.exp(last[:, :, :, None] - total)).astype(dtype)
    kept = jnp.exp(last)

    def chunk(state, inp):
        w_c, u_c, q_c, scores_c, k_c, kept_c = inp
        handed = state.astype(dtype)
        u = u_c - jnp.einsum("bhld,bhdp->bhlp", w_c.astype(dtype), handed,
                             preferred_element_type=f32)
        o = jnp.einsum("bhld,bhdp->bhlp", q_c, handed,
                       preferred_element_type=f32) \
            + jnp.einsum("bhls,bhsp->bhlp", scores_c, u.astype(dtype),
                         preferred_element_type=f32)
        state = kept_c[..., None] * state + jnp.einsum(
            "bhsd,bhsp->bhdp", k_c, u.astype(dtype),
            preferred_element_type=f32)
        return state, o.astype(dtype)

    state, o = lax.scan(chunk, state, tuple(
        jnp.moveaxis(a, 1, 0) for a in (w, u_own, q_dec, scores, k_end,
                                        kept)))
    return state, jnp.moveaxis(o, (0, 2), (1, 3))         # (b,c,L,h,p)


def kda_chunked(q, k, v, g, beta, chunk):
    """The same map as :func:`kda_recurrence`, ``chunk`` tokens at a time
    (the module's docstring has the algebra).  ``g`` must not be under
    ``-MAX_EXPONENT / min(chunk, SUB_BLOCK)`` (the bounded gate's -5 at
    sub-blocks of 16).  A sequence that is no multiple of the chunk, or of
    :data:`SCAN_BLOCK_CHUNKS` chunks where it is longer than that, is padded
    at its end with tokens of ``g = 0``, ``beta = 0`` and ``k = 0``, which
    no earlier token sees."""
    b, t, h, e = q.shape
    sub = min(SUB_BLOCK, chunk)
    if chunk % sub:
        raise ValueError("the chunk %d is no multiple of %d" % (chunk, sub))
    chunks = -(-t // chunk)
    per_block = min(SCAN_BLOCK_CHUNKS, chunks)
    blocks = -(-chunks // per_block)
    padded = blocks * per_block * chunk

    def split(a):
        a = jnp.pad(a, [(0, 0), (0, padded - t)] + [(0, 0)] * (a.ndim - 2))
        return jnp.moveaxis(a.reshape(
            (b, blocks, per_block, chunk) + a.shape[2:]), 1, 0)

    f32 = jnp.float32
    xs = (split(q), split(k), split(v), split(g.astype(f32)),
          split(beta.astype(f32)))
    block = jax.checkpoint(functools.partial(_block, sub=sub))
    _, o = lax.scan(block, jnp.zeros((b, h, e, v.shape[-1]), f32), xs)
    return jnp.moveaxis(o, 0, 1).reshape(b, padded, h, -1)[:, :t]


def scan_kernel_tiles(cfg, dtype):
    """Whether :func:`kda_mixer` spells a scan of ``cfg``'s sizes in
    ``dtype`` as the kernel pair of ``ops/kda_kernels.py``."""
    return kda_kernels.tiles(cfg.kda_chunk, cfg.n_heads, cfg.kda_head_dim,
                             dtype)


def _l2_normed(x, eps=1e-6):
    """``x / sqrt(sum x^2 + eps)`` over the last axis, in float32."""
    xf = x.astype(jnp.float32)
    return (xf * lax.rsqrt(jnp.sum(jnp.square(xf), axis=-1, keepdims=True)
                           + eps)).astype(x.dtype)


def kda_mixer(lp, x, cfg):
    """One mixer over the normed residual ``x`` (b, t, d).  ``lp`` holds the
    layer's leaves by kind; ``cfg`` gives ``n_heads``, ``kda_head_dim``,
    ``kda_chunk``, ``kda_lower_bound`` (which ``HybridLMConfig`` holds to
    what a sub-block's exponents can carry) and ``norm_eps``."""
    h, e = cfg.n_heads, cfg.kda_head_dim
    b, t, _ = x.shape
    f32 = jnp.float32
    with jax.named_scope("kda_in_proj"):
        q, k, v, f = (checkpoint_name(x @ lp[w], PROJECTION)
                      for w in ("kda_wq", "kda_wk", "kda_wv", "kda_wf"))
        beta, gate = x @ lp["kda_wb"], x @ lp["kda_wg"]   # (b, t, h)
    with jax.named_scope("kda_conv"):
        no_bias = jnp.zeros((h * e,), x.dtype)
        q, k, v = (jax.nn.silu(causal_conv1d(a, lp[w], no_bias)
                               ).reshape(b, t, h, e)
                   for a, w in ((q, "kda_conv_q"), (k, "kda_conv_k"),
                                (v, "kda_conv_v")))
        q = _l2_normed(q) * jnp.asarray(e ** -0.5, q.dtype)
        k = _l2_normed(k)
    with jax.named_scope("kda_gate"):
        rate = jnp.exp(lp["kda_a_log"].astype(f32))[:, None]
        g = cfg.kda_lower_bound * jax.nn.sigmoid(rate * (
            f.astype(f32) + lp["kda_dt_bias"].astype(f32)
        ).reshape(b, t, h, e))
        beta = jax.nn.sigmoid(beta.astype(f32))
    with jax.named_scope("kda_scan"):
        if scan_kernel_tiles(cfg, q.dtype):
            o = kda_kernels.kda_scan(q, k, v, g, beta)
        else:
            o = kda_chunked(q, k, v, g, beta, cfg.kda_chunk)
    with jax.named_scope("kda_out_norm"):
        o = rms_norm(o.reshape(b, t, h * e), lp["kda_norm"], cfg.norm_eps)
        o = (o.reshape(b, t, h, e) * jax.nn.sigmoid(
            gate.astype(f32))[..., None].astype(o.dtype)
             ).reshape(b, t, h * e)
    with jax.named_scope("kda_out_proj"):
        return checkpoint_name(o @ lp["kda_wo"], PROJECTION)
