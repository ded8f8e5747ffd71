"""Pallas TPU kernels for the hot ops XLA doesn't fuse optimally.

Reference equivalence: these replace the reference's hand-written CUDA /
cuDNN kernels (SURVEY.md §2.1 "cuDNN integration") for the memory-bound
attention path.  Flash attention streams K/V blocks through VMEM with an
online softmax so the (T×T) score matrix never materializes in HBM —
the standard TPU flash pattern (see /opt/skills/guides/pallas_guide.md).

On non-TPU backends the same kernel runs in Pallas interpret mode, so
tests exercise the real kernel logic on the CPU mesh.  That choice is
made in exactly one place, :func:`resolve_interpret`; every kernel in
the package (here, ``fused_optimizer.py``, ``generated_kernels.py``,
``ssd_kernels.py``) goes through it.

Training: forward AND backward are Pallas kernels.  The forward emits the
per-row logsumexp; the backward recomputes probabilities blockwise from
(q, k, lse) with the standard two-kernel split (dq over k-blocks, dk/dv
over q-blocks), so the (T×T) score matrix never exists in HBM in either
direction — backward HBM is O(T·D), matching the flash-attention paper's
recomputation scheme.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from .registry import register

_NEG_INF = -1e30


def _sds(shape, dtype, like):
    """ShapeDtypeStruct inheriting `like`'s varying-mesh-axes (vma) type,
    so the kernels compose with shard_map's check_vma typing."""
    vma = jax.typeof(like).vma
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def on_tpu():
    """Is the default backend — where un-pinned arrays and jitted
    programs land — a TPU?  A backend that fails to initialise raises
    here; it never reads as "no TPU"."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret=None):
    """The package's one compile-or-interpret decision for a Pallas
    kernel: an explicit ``interpret`` wins; ``None`` means Mosaic on a
    TPU and the Pallas interpreter everywhere else."""
    if interpret is None:
        return not on_tpu()
    return bool(interpret)


def _attention_reference(q, k, v, causal, scale):
    """jnp reference: q/k/v (BH, T, D)."""
    s = jnp.einsum("btd,bsd->bts", q, k) * scale
    if causal:
        t = q.shape[1]
        mask = jnp.arange(t)[:, None] >= jnp.arange(s.shape[-1])[None, :]
        s = jnp.where(mask[None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bts,bsd->btd", p, v)


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
               causal, scale, block_q, block_k, num_k_blocks, t_k):
    from jax.experimental import pallas as pl

    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _step():
        q = q_ref[0]                                   # (Bq, D)
        k = k_ref[0]                                   # (Bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (Bq, Bk)
        kpos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        # mask the ragged tail of the last K block (grid padding)
        valid = kpos < t_k
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            valid = valid & (qpos >= kpos)
        s = jnp.where(valid, s, _NEG_INF)
        m_prev = m_scr[:, :1]                          # (Bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        m_safe = jnp.where(m_new <= _NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(s - m_safe)
        p = jnp.where(s <= _NEG_INF / 2, 0.0, p)
        corr = jnp.exp(jnp.where(m_prev <= _NEG_INF / 2, _NEG_INF, m_prev)
                       - m_safe)
        corr = jnp.where(m_prev <= _NEG_INF / 2, 0.0, corr)
        l_scr[:, :1] = l_scr[:, :1] * corr + jnp.sum(p, axis=-1,
                                                     keepdims=True)
        # zero padded V rows: p is 0 there, but 0 × garbage/NaN = NaN
        vrow_ok = (ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0)) < t_k
        v_blk = jnp.where(vrow_ok, v_ref[0], 0.0)
        pv = jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * corr + pv
        m_scr[:, :1] = m_new

    if causal:
        # skip blocks strictly above the diagonal
        @pl.when(qi * block_q + block_q - 1 >= ki * block_k)
        def _():
            _step()
    else:
        _step()

    @pl.when(ki == num_k_blocks - 1)
    def _finish():
        denom = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0] = (acc_scr[:] / denom).astype(o_ref.dtype)
        # per-row logsumexp for the backward recompute: lse = m + log(l).
        # The 8-row broadcast satisfies the TPU (8, 128) tile constraint on
        # the (BH, 8, T) lse buffer.
        row = (m_scr[:, :1] + jnp.log(denom))[:, 0]
        lse_ref[0] = jnp.broadcast_to(row[None, :], lse_ref[0].shape)


def _flash_attention_fwd_impl(q, k, v, causal, scale, block_q, block_k,
                              interpret):
    """q/k/v: (BH, T, D) → (BH, T, D)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, T, D = q.shape
    Tk = k.shape[1]
    block_q = min(block_q, T)
    block_k = min(block_k, Tk)
    nq = pl.cdiv(T, block_q)
    nk = pl.cdiv(Tk, block_k)

    kernel = functools.partial(
        _fa_kernel, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, num_k_blocks=nk, t_k=Tk)

    return pl.pallas_call(
        kernel,
        out_shape=(_sds((BH, T, D), q.dtype, q),
                   _sds((BH, 8, T), jnp.float32, q)),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=(pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
                   pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i))),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max
            pltpu.VMEM((block_q, 128), jnp.float32),   # running sum
            pltpu.VMEM((block_q, D), jnp.float32),     # output accumulator
        ],
        name="_fa_kernel",
        interpret=resolve_interpret(interpret),
    )(q, k, v)


# ---------------------------------------------------------------------------
# Backward kernels: probabilities are recomputed blockwise from (q, k, lse);
# delta = rowsum(dO ⊙ O) folds the softmax normalization gradient.
# ---------------------------------------------------------------------------
def _fa_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                  dq_scr, *, causal, scale, block_q, block_k, num_k_blocks,
                  t_q, t_k):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _step():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = (qpos < t_q) & (kpos < t_k)
        if causal:
            valid = valid & (qpos >= kpos)
        lse = lse_ref[0, 0][:, None]                   # (Bq, 1)
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)
        # zero the grid-padding garbage before it enters a matmul
        # (0 x inf/NaN = NaN would otherwise leak through p's zeros)
        qrow_ok = (qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)) < t_q
        krow_ok = (ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0)) < t_k
        do_blk = jnp.where(qrow_ok, do_ref[0].astype(jnp.float32), 0.0)
        v_blk = jnp.where(krow_ok, v_ref[0].astype(jnp.float32), 0.0)
        dp = jax.lax.dot_general(
            do_blk, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # (Bq, Bk)
        ds = jnp.where(valid, p * (dp - delta_ref[0, 0][:, None]), 0.0)
        k_blk = jnp.where(krow_ok, k.astype(jnp.float32), 0.0)
        dq_scr[:] += jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    if causal:
        @pl.when(qi * block_q + block_q - 1 >= ki * block_k)
        def _():
            _step()
    else:
        _step()

    @pl.when(ki == num_k_blocks - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _fa_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dk_ref, dv_ref, dk_scr, dv_scr, *, causal, scale,
                   block_q, block_k, num_q_blocks, t_q, t_k):
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _step():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = (qpos < t_q) & (kpos < t_k)
        if causal:
            valid = valid & (qpos >= kpos)
        lse = lse_ref[0, 0][:, None]
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)     # (Bq, Bk)
        qrow_ok = (qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)) < t_q
        krow_ok = (ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0)) < t_k
        do = jnp.where(qrow_ok, do_ref[0].astype(jnp.float32), 0.0)
        q_blk = jnp.where(qrow_ok, q.astype(jnp.float32), 0.0)
        v_blk = jnp.where(krow_ok, v_ref[0].astype(jnp.float32), 0.0)
        dv_scr[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # (Bk, D)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # (Bq, Bk)
        ds = jnp.where(valid, p * (dp - delta_ref[0, 0][:, None]), 0.0)
        dk_scr[:] += jax.lax.dot_general(
            ds, q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (Bk, D)

    if causal:
        # skip q blocks entirely above the diagonal for this k block
        @pl.when(qi * block_q + block_q - 1 >= ki * block_k)
        def _():
            _step()
    else:
        _step()

    @pl.when(qi == num_q_blocks - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _tile_rows(x):
    """(BH, T) → (BH, 8, T): the sublane-broadcast tile layout the kernels
    read per-row scalars from."""
    BH, T = x.shape
    return jnp.broadcast_to(x[:, None, :], (BH, 8, T))


def flash_delta(o, do):
    """softmax-normalization gradient delta = rowsum(dO ⊙ O), (BH, T) f32."""
    return jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)


def flash_dq(q, k, v, do, lse, delta, causal, scale, block_q=128,
             block_k=128, interpret=None):
    """dq for one (q-block × k-chunk) pairing; lse/delta are (BH, T) f32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, T, D = q.shape
    Tk = k.shape[1]
    block_q = min(block_q, T)
    block_k = min(block_k, Tk)
    nq = pl.cdiv(T, block_q)
    nk = pl.cdiv(Tk, block_k)
    q_spec = pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0))
    k_spec = pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0))
    row_q = pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i))
    return pl.pallas_call(
        functools.partial(_fa_dq_kernel, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k, num_k_blocks=nk,
                          t_q=T, t_k=Tk),
        out_shape=_sds((BH, T, D), q.dtype, q),
        grid=(BH, nq, nk),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_q, row_q],
        out_specs=q_spec,
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        name="_fa_dq_kernel",
        interpret=resolve_interpret(interpret),
    )(q, k, v, do, _tile_rows(lse), _tile_rows(delta))


def flash_dkv(q, k, v, do, lse, delta, causal, scale, block_q=128,
              block_k=128, interpret=None):
    """(dk, dv) for one (q-chunk × k-block) pairing; k-major grid so q is
    the accumulation axis."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, T, D = q.shape
    Tk = k.shape[1]
    block_q = min(block_q, T)
    block_k = min(block_k, Tk)
    nq = pl.cdiv(T, block_q)
    nk = pl.cdiv(Tk, block_k)
    q_spec = pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0))
    k_spec = pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0))
    row_q = pl.BlockSpec((1, 8, block_q), lambda b, j, i: (b, 0, i))
    return pl.pallas_call(
        functools.partial(_fa_dkv_kernel, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k, num_q_blocks=nq,
                          t_q=T, t_k=Tk),
        out_shape=(_sds((BH, Tk, D), k.dtype, q),
                   _sds((BH, Tk, D), v.dtype, q)),
        grid=(BH, nk, nq),
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_q, row_q],
        out_specs=(k_spec, k_spec),
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        name="_fa_dkv_kernel",
        interpret=resolve_interpret(interpret),
    )(q, k, v, do, _tile_rows(lse), _tile_rows(delta))


def flash_forward_with_lse(q, k, v, causal, scale, interpret=None):
    """(out, lse) with lse (BH, T) f32 — building block for ring attention."""
    out, lse8 = _flash_attention_fwd_impl(q, k, v, causal, scale,
                                          block_q=128, block_k=128,
                                          interpret=interpret)
    return out, lse8[:, 0, :]


def _flash_attention_bwd_impl(q, k, v, o, lse, do, causal, scale, block_q,
                              block_k, interpret):
    delta = flash_delta(o, do)
    lse2 = lse[:, 0, :]
    dq = flash_dq(q, k, v, do, lse2, delta, causal, scale, block_q, block_k,
                  interpret)
    dk, dv = flash_dkv(q, k, v, do, lse2, delta, causal, scale, block_q,
                       block_k, interpret)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_core(q, k, v, causal, scale):
    out, _ = _flash_attention_fwd_impl(q, k, v, causal, scale,
                                       block_q=128, block_k=128,
                                       interpret=None)
    return out


def _flash_fwd(q, k, v, causal, scale):
    out, lse = _flash_attention_fwd_impl(q, k, v, causal, scale,
                                         block_q=128, block_k=128,
                                         interpret=None)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, res, g):
    q, k, v, o, lse = res
    return _flash_attention_bwd_impl(q, k, v, o, lse, g, causal, scale,
                                     block_q=128, block_k=128,
                                     interpret=None)


_flash_core.defvjp(_flash_fwd, _flash_bwd)


@register("_contrib_flash_attention", arg_names=["query", "key", "value"],
          aliases=("flash_attention",))
def flash_attention(query, key, value, causal=False, scale=None):
    """Flash attention over (B, T, H, D) tensors (Pallas TPU kernel).

    Memory O(T) instead of O(T²); the per-(batch, head) score blocks live
    only in VMEM.  Works on any backend (interpret mode off-TPU)."""
    B, T, H, D = query.shape
    Tk = key.shape[1]
    if scale is None:
        scale = D ** -0.5

    def to_bh(x, t):
        return x.transpose(0, 2, 1, 3).reshape(B * H, t, x.shape[-1])

    out = _flash_core(to_bh(query, T), to_bh(key, Tk), to_bh(value, Tk),
                      bool(causal), float(scale))
    return out.reshape(B, H, T, D).transpose(0, 2, 1, 3)


# ---------------------------------------------------------------------------
# int8 matmul with fused requantize epilogue (reference equivalence:
# src/operator/quantization/quantized_conv.cu + requantize.cu — cuDNN int8
# conv followed by a separate requantize kernel; here one Pallas kernel
# does s8xs8->s32 on the MXU and scales/bias/relu/rounds back to int8 in
# VMEM, so the int32 accumulator never touches HBM)
# ---------------------------------------------------------------------------
def _qmm_requant_kernel(x_ref, w_ref, bias_ref, o_ref, *, out_scale,
                        relu, nsteps):
    """One (Mb, Nb) output tile: accumulate s32 over K-blocks (unrolled —
    K/512 is <=4 for resnet), then the epilogue: acc*scale + bias ->
    [relu] -> round -> clip -> int8."""
    acc = None
    for step in range(nsteps):
        xk = x_ref[:, step * _QMM_KB:(step + 1) * _QMM_KB]
        wk = w_ref[step * _QMM_KB:(step + 1) * _QMM_KB, :]
        part = jax.lax.dot_general(xk, wk, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.int32)
        acc = part if acc is None else acc + part
    real = acc.astype(jnp.float32) * out_scale + bias_ref[:]
    if relu:
        real = jnp.maximum(real, 0.0)
    o_ref[:, :] = jnp.clip(jnp.round(real), -127, 127).astype(jnp.int8)


_QMM_MB = 512
_QMM_NB = 256
_QMM_KB = 512


def qmm_requant(x, w, bias, out_scale, relu=True, interpret=None):
    """int8 (M, K) x (K, N) -> int8 (M, N) with the requantize epilogue
    fused: out = clip(round(relu(acc * out_scale + bias))).

    ``out_scale`` folds s_x * s_w / s_out; ``bias`` is fp32 in the
    *output-quantized* domain (already divided by s_out).  Shapes are
    padded to tile multiples; K must fit VMEM blocks of _QMM_KB.
    """
    from jax.experimental import pallas as pl

    M, K = x.shape
    N = w.shape[1]

    def rup(v, m):
        return (v + m - 1) // m * m

    Mp, Kp, Np = rup(M, _QMM_MB), rup(K, _QMM_KB), rup(N, _QMM_NB)
    if (Mp, Kp) != (M, K):
        x = jnp.pad(x, ((0, Mp - M), (0, Kp - K)))
    if (Kp, Np) != (K, N):
        w = jnp.pad(w, ((0, Kp - K), (0, Np - N)))
    bias = jnp.pad(bias.astype(jnp.float32), (0, Np - N)) \
        .reshape(1, Np)

    kernel = functools.partial(
        _qmm_requant_kernel, out_scale=float(out_scale), relu=bool(relu),
        nsteps=Kp // _QMM_KB)
    out = pl.pallas_call(
        kernel,
        grid=(Mp // _QMM_MB, Np // _QMM_NB),
        in_specs=[
            pl.BlockSpec((_QMM_MB, Kp), lambda i, j: (i, 0)),
            pl.BlockSpec((Kp, _QMM_NB), lambda i, j: (0, j)),
            pl.BlockSpec((1, _QMM_NB), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((_QMM_MB, _QMM_NB), lambda i, j: (i, j)),
        out_shape=_sds((Mp, Np), jnp.int8, x),
        name="_qmm_requant_kernel",
        interpret=resolve_interpret(interpret),
    )(x, w, bias)
    return out[:M, :N]


@register("_contrib_quantized_conv_requant",
          arg_names=["data", "weight", "bias"], differentiable=False,
          num_outputs=3, optional_args=("bias",))
def quantized_conv_requant(data, weight, bias=None, kernel=(), stride=(),
                           dilate=(), pad=(), num_filter=0, num_group=1,
                           layout=None, in_scale=1.0, w_scale=1.0,
                           out_scale=1.0, relu=True,
                           min_calib_range=None, max_calib_range=None):
    """Fused int8 conv + bias + [relu] + requantize -> int8 (the
    quantize_graph_pass fusion target).  Scales are real-domain:
    ``x_real = x_int * in_scale`` etc.; output ints are
    ``round(real / out_scale)``.

    NHWC 1x1 stride-1 convs lower to the Pallas MXU kernel (the int32
    accumulator stays in VMEM); everything else uses the XLA int8 conv
    with the epilogue fused by XLA."""
    from jax import lax
    from .nn import _tup, _conv_layout

    nsp = len(kernel) if kernel else data.ndim - 2
    stride = _tup(stride, nsp) if stride else (1,) * nsp
    dilate = _tup(dilate, nsp) if dilate else (1,) * nsp
    pad = _tup(pad, nsp) if pad else (0,) * nsp
    dimnum, channels_last = _conv_layout(layout, nsp)
    x = data.astype(jnp.int8)
    w = weight.astype(jnp.int8)
    scale = float(in_scale) * float(w_scale) / float(out_scale)
    if bias is None:
        bias_q = jnp.zeros((int(num_filter),), jnp.float32)
    else:
        bias_q = bias.astype(jnp.float32) / float(out_scale)

    if (channels_last and all(k == 1 for k in kernel) and num_group == 1
            and all(p == 0 for p in pad)):
        if any(s != 1 for s in stride):
            sl = (slice(None),) + tuple(slice(None, None, s)
                                       for s in stride)
            x = x[sl]
        sp_shape = x.shape[:-1]
        xf = x.reshape(-1, x.shape[-1])
        wf = w.reshape(w.shape[0], w.shape[-1]).T  # (K, N)
        import os as _os
        if _os.environ.get("MXTPU_PALLAS_QMM", "0") == "1":
            # opt-in: the Pallas kernel wins on CPU-interpret correctness
            # tests but XLA's int8 dot out-tiles it at resnet's large-M
            # small-K shapes (measured 22 vs 55 ms at M=800k K=64) — the
            # epilogue below fuses into the dot either way
            out = qmm_requant(xf, wf, bias_q, scale, relu=relu)
        else:
            acc = jax.lax.dot_general(
                xf, wf, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
            real = acc.astype(jnp.float32) * scale + bias_q
            if relu:
                real = jnp.maximum(real, 0.0)
            out = jnp.clip(jnp.round(real), -127, 127).astype(jnp.int8)
        return (out.reshape(sp_shape + (w.shape[0],)),) + _qcr_range(
            out_scale, min_calib_range, max_calib_range)

    dn = lax.conv_dimension_numbers(data.shape, weight.shape, dimnum)
    acc = lax.conv_general_dilated(
        x, w, window_strides=stride, padding=[(p, p) for p in pad],
        rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=int(num_group),
        preferred_element_type=jnp.int32)
    bshape = (1,) * (acc.ndim - 1) + (-1,) if channels_last \
        else (1, -1) + (1,) * nsp
    real = acc.astype(jnp.float32) * scale + bias_q.reshape(bshape)
    if relu:
        real = jnp.maximum(real, 0.0)
    q = jnp.clip(jnp.round(real), -127, 127).astype(jnp.int8)
    return (q,) + _qcr_range(out_scale, min_calib_range, max_calib_range)


def _qcr_range(out_scale, lo, hi):
    """(min, max) companion outputs so downstream quantized consumers can
    keep reading the (data, min, max) triple ABI."""
    if lo is None:
        hi = float(out_scale) * 127.0
        lo = -hi
    return (jnp.asarray([float(lo)], jnp.float32),
            jnp.asarray([float(hi)], jnp.float32))


# ---------------------------------------------------------------------------
# implicit-GEMM 3x3 conv with fused epilogue (reference equivalence:
# src/operator/quantization/quantized_conv.cu — cuDNN's implicit-GEMM int8
# conv — and src/operator/nn/convolution.cu for the float path).  The
# kernel stages an im2col patch matrix in VMEM (K = 9*Cin feeds the MXU a
# full-depth contraction instead of nine K=Cin dots), accumulates in
# int32/f32, and runs the epilogue (requantize, or BN-scale+relu) before
# the tile ever leaves VMEM — the accumulator never touches HBM.
# ---------------------------------------------------------------------------
def _conv3x3_kernel(x_ref, w_ref, scale_ref, shift_ref, o_ref, xpatch, col,
                    sem, *, nb, th, w_out, cin, relu, out_dtype, acc_dtype):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, h, co = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    # DMA the (nb, th+2, Wp, Cin) input patch once per (n, h); reuse it
    # across the Cout grid axis (co is innermost, scratch persists).
    # Wp/Cin are pre-padded by the wrapper to sublane (8) / lane (128)
    # multiples — Mosaic rejects misaligned second-minor/minor dims here.
    @pl.when(co == 0)
    def _load():
        dma = pltpu.make_async_copy(
            x_ref.at[pl.ds(n * nb, nb), pl.ds(h * th, th + 2)],
            xpatch, sem)
        dma.start()
        dma.wait()
        # build the im2col matrix: rows = output positions of this tile,
        # cols = the 3x3xCin receptive field
        xp = xpatch[...]
        for dy in range(3):
            for dx in range(3):
                tap = xp[:, dy:dy + th, dx:dx + w_out, :]
                col[:, (dy * 3 + dx) * cin:(dy * 3 + dx + 1) * cin] = \
                    tap.reshape(nb * th * w_out, cin)

    acc = jax.lax.dot_general(
        col[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=acc_dtype)
    real = acc.astype(jnp.float32) * scale_ref[...] + shift_ref[...]
    if relu:
        real = jnp.maximum(real, 0.0)
    if out_dtype == jnp.int8:
        real = jnp.clip(jnp.round(real), -127, 127)
    o_ref[...] = real.reshape(nb, th, w_out, -1).astype(out_dtype)


def conv3x3_epilogue(x, w, scale, shift, relu=True, out_dtype=None,
                     nb=None, th=None, tn=None, interpret=None):
    """3x3 stride-1 same-pad NHWC conv with a fused affine epilogue:
    ``out = cast(relu(conv(x, w) * scale + shift))``.

    - int8 x / int8 w: MXU s8xs8->s32; ``scale`` folds the requantize
      (s_x*s_w/s_out), ``shift`` the bias; out_dtype int8 (rounded).
    - bf16 x / bf16 w: f32 accumulate; ``scale``/``shift`` fold inference
      BatchNorm; out_dtype bf16.

    x: (N, H, W, Cin); w: (3, 3, Cin, Cout); scale/shift: (Cout,).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    N, H, W, Cin = x.shape
    Cout = w.shape[-1]
    is_int8 = x.dtype == jnp.int8
    acc_dtype = jnp.int32 if is_int8 else jnp.float32
    if out_dtype is None:
        out_dtype = jnp.int8 if is_int8 else x.dtype

    # tile choices: rows-per-tile scales down as W grows so the GEMM's M
    # stays ~mxu-sized; images-per-tile then batches M up to ~1k rows
    # (fewer, fatter grid steps — each step amortizes its DMA + epilogue)
    explicit_th, explicit_nb = th is not None, nb is not None
    if th is None:
        th = max(1, min(H, 448 // W))
    while H % th:
        th -= 1
    if nb is None:
        nb = max(1, 1024 // (th * W))
        while N % nb:
            nb -= 1
    if tn is None:
        tn = min(max(Cout, 128), 256)
    tn = -(-tn // 128) * 128  # full 128-lane multiple (Mosaic minor dim)

    # VMEM budget clamp: the col scratch (nb*th*W, 9*Cp) dominates and
    # grows with Cin, so H/W-only tile sizing could overflow VMEM at
    # large channel counts (Cin=512 bf16 ≈ 12MB+) and die at Mosaic
    # compile time.  Auto-chosen tiles shrink to fit; explicit tiles
    # that cannot fit fail loudly here instead.
    Wp_est = -(-(W + 2) // 8) * 8
    Cp_est = -(-Cin // 128) * 128
    itemsize = jnp.dtype(x.dtype).itemsize
    osize = jnp.dtype(out_dtype).itemsize

    def _tile_bytes(nb_, th_):
        xpatch = nb_ * (th_ + 2) * Wp_est * Cp_est * itemsize
        col = nb_ * th_ * W * 9 * Cp_est * itemsize
        wblk = 9 * Cp_est * tn * itemsize
        outblk = nb_ * th_ * W * tn * osize
        accblk = nb_ * th_ * W * tn * 4  # f32/i32 accumulator
        return xpatch + col + wblk + outblk + accblk

    budget = int(os.environ.get("MXTPU_PALLAS_VMEM_BUDGET",
                                12 * 1024 * 1024))
    # auto-chosen tiles shrink to fit; only user-passed ones fail loudly
    if not explicit_nb:
        while _tile_bytes(nb, th) > budget and nb > 1:
            nb -= 1
            while N % nb:
                nb -= 1
    if not explicit_th:
        while _tile_bytes(nb, th) > budget and th > 1:
            th -= 1
            while H % th:
                th -= 1
    if _tile_bytes(nb, th) > budget:
        raise ValueError(
            "conv3x3_epilogue tiles nb=%d th=%d need %d bytes of VMEM "
            "(budget %d) at W=%d Cin=%d Cout=%d%s — shrink nb/th or raise "
            "MXTPU_PALLAS_VMEM_BUDGET" %
            (nb, th, _tile_bytes(nb, th), budget, W, Cin, Cout,
             "" if (explicit_nb or explicit_th)
             else " even at the smallest auto tiling"))

    # Mosaic alignment: the scratch's second-minor dim (patch width) must
    # be a sublane multiple and its minor dims (channels in / out) full
    # 128-lane multiples — pad with zeros (padded channels contribute 0
    # to the dot, padded columns are never addressed by any tap)
    Wp = -(-(W + 2) // 8) * 8
    Cp = -(-Cin // 128) * 128
    Cop = -(-Cout // tn) * tn
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, Wp - W - 1), (0, Cp - Cin)))
    wp = jnp.pad(w, ((0, 0), (0, 0), (0, Cp - Cin), (0, Cop - Cout)))
    wcol = wp.reshape(9 * Cp, Cop)
    scale = jnp.pad(jnp.asarray(scale, jnp.float32),
                    (0, Cop - Cout)).reshape(1, Cop)
    shift = jnp.pad(jnp.asarray(shift, jnp.float32),
                    (0, Cop - Cout)).reshape(1, Cop)

    kernel = functools.partial(
        _conv3x3_kernel, nb=nb, th=th, w_out=W, cin=Cp, relu=bool(relu),
        out_dtype=out_dtype, acc_dtype=acc_dtype)
    out = pl.pallas_call(
        kernel,
        grid=(N // nb, H // th, Cop // tn),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # manual halo DMA
            pl.BlockSpec((9 * Cp, tn), lambda n, h, co: (0, co)),
            pl.BlockSpec((1, tn), lambda n, h, co: (0, co)),
            pl.BlockSpec((1, tn), lambda n, h, co: (0, co)),
        ],
        out_specs=pl.BlockSpec((nb, th, W, tn),
                               lambda n, h, co: (n, h, 0, co)),
        out_shape=_sds((N, H, W, Cop), out_dtype, x),
        scratch_shapes=[
            pltpu.VMEM((nb, th + 2, Wp, Cp), x.dtype),
            pltpu.VMEM((nb * th * W, 9 * Cp), x.dtype),
            pltpu.SemaphoreType.DMA(()),
        ],
        name="_conv3x3_kernel",
        interpret=resolve_interpret(interpret),
    )(xp, wcol, scale, shift)
    return out if Cop == Cout else out[..., :Cout]


# ---------------------------------------------------------------------------
# declared cost models (analysis/cost.py KERNEL_COSTS): pallas_call's
# body traces once — not once per grid step — so the tape consults these
# shape-arithmetic models instead (docs/fusion.md "kernel cost
# declaration contract").  bytes model the BLOCKED access pattern: a
# block re-fetched per grid step along an axis bills once per step.
# ---------------------------------------------------------------------------
from ..analysis.cost import declare_kernel_cost as _declare_cost
from ..analysis.cost import _grid_of


def _nbytes(aval):
    import numpy as _onp
    n = 1
    for d in getattr(aval, "shape", ()):
        n *= int(d)
    return n * _onp.dtype(aval.dtype).itemsize


def _out_bytes(eqn):
    return sum(_nbytes(v.aval) for v in eqn.outvars)


@_declare_cost("_fa_kernel")
def _cost_fa_fwd(eqn):
    q, k, v = (a.aval for a in eqn.invars[:3])
    bh, t, d = (int(x) for x in q.shape)
    tk = int(k.shape[1])
    grid = _grid_of(eqn)
    nq = grid[1] if len(grid) == 3 else 1
    return {
        # qk^T and pv dots (causal masking not discounted: upper bound)
        "flops": 4 * bh * t * tk * d,
        "transcendentals": bh * t * tk + bh * t,      # exp + final log
        # q resident across the inner k sweep; k/v re-fetched per q block
        "bytes_read": _nbytes(q) + nq * (_nbytes(k) + _nbytes(v)),
        "bytes_written": _out_bytes(eqn),             # out + lse
    }


@_declare_cost("_fa_dq_kernel")
def _cost_fa_dq(eqn):
    q, k, v, do = (a.aval for a in eqn.invars[:4])
    bh, t, d = (int(x) for x in q.shape)
    tk = int(k.shape[1])
    grid = _grid_of(eqn)
    nq = grid[1] if len(grid) == 3 else 1
    rows = sum(_nbytes(a.aval) for a in eqn.invars[4:6])   # lse, delta
    return {
        "flops": 6 * bh * t * tk * d,                 # s, dp, ds·k dots
        "transcendentals": bh * t * tk,               # p recompute
        "bytes_read": _nbytes(q) + _nbytes(do) + rows
        + nq * (_nbytes(k) + _nbytes(v)),
        "bytes_written": _out_bytes(eqn),             # dq
    }


@_declare_cost("_fa_dkv_kernel")
def _cost_fa_dkv(eqn):
    q, k, v, do = (a.aval for a in eqn.invars[:4])
    bh, t, d = (int(x) for x in q.shape)
    tk = int(k.shape[1])
    grid = _grid_of(eqn)
    nk = grid[1] if len(grid) == 3 else 1
    rows = sum(_nbytes(a.aval) for a in eqn.invars[4:6])
    return {
        "flops": 8 * bh * t * tk * d,          # s, dv, dp, dk dots
        "transcendentals": bh * t * tk,
        "bytes_read": _nbytes(k) + _nbytes(v)
        + nk * (_nbytes(q) + _nbytes(do) + rows),
        "bytes_written": _out_bytes(eqn),      # dk + dv
    }


@_declare_cost("_qmm_requant_kernel")
def _cost_qmm(eqn):
    x, w = eqn.invars[0].aval, eqn.invars[1].aval
    m, kk = (int(d) for d in x.shape)
    n = int(w.shape[1])
    grid = _grid_of(eqn)
    ni = grid[0] if len(grid) == 2 else 1
    nj = grid[1] if len(grid) == 2 else 1
    return {
        "flops": 2 * m * n * kk + 3 * m * n,   # MXU dot + epilogue
        "transcendentals": 0,
        # x streamed once per N tile, w once per M tile, bias per tile
        "bytes_read": nj * _nbytes(x) + ni * _nbytes(w)
        + ni * _nbytes(eqn.invars[2].aval),
        "bytes_written": _out_bytes(eqn),
    }


@_declare_cost("_conv3x3_kernel")
def _cost_conv3x3(eqn):
    xp, wcol = eqn.invars[0].aval, eqn.invars[1].aval
    out = eqn.outvars[0].aval
    cin9 = int(wcol.shape[0])                  # 9 * Cp
    out_n = 1
    for d in out.shape:
        out_n *= int(d)
    grid = _grid_of(eqn)
    nh_tiles = (grid[0] * grid[1]) if len(grid) == 3 else 1
    return {
        "flops": 2 * out_n * cin9 + 2 * out_n,  # im2col GEMM + epilogue
        "transcendentals": 0,
        # the halo patch DMAs once per (n, h) tile (co reuses it); the
        # weight/scale/shift tiles stream once per (n, h) tile
        "bytes_read": _nbytes(xp)
        + nh_tiles * (_nbytes(wcol) + _nbytes(eqn.invars[2].aval)
                      + _nbytes(eqn.invars[3].aval)),
        "bytes_written": _out_bytes(eqn),
    }
