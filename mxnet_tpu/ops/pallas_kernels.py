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

Training: forward AND backward are Pallas kernels, one family
(:func:`flash_forward`, :func:`flash_backward`, :func:`flash_mha`) for
``transformer/hybrid.py``'s causal attention, ``parallel/ring_attention.py``'s
hops and the ``_contrib_flash_attention`` operator.  Queries and keys of
``e_qk`` columns, values and output of ``e_v``; ``heads / kv_heads`` query
heads read one key-value head inside one grid step, so ``dk`` and ``dv`` sum
over the group in VMEM; the blocks are arguments (:func:`flash_tiles` gives
``HybridLM`` its blocks from the shapes, or declines them).  The forward
emits the per-row logsumexp; the backward recomputes probabilities blockwise
from (q, k, lse) with the standard two-kernel split (dq over k-blocks, dk/dv
over q-blocks), so the (T×T) score matrix never exists in HBM in either
direction.  Under causality a block wholly above the diagonal neither runs
nor is fetched (its grid step asks for the block the last step that ran
held), and only the blocks the diagonal crosses pay for the mask.

**Precision, operand by operand** (the einsum spelling's,
``hybrid._attend_rows``, no lower and no other).  Every product takes its
operands in the arrays' dtype (bfloat16 in the benchmark's cells, float32
in the tests: nothing is cast up first) and accumulates in float32
(``preferred_element_type``): ``q k^T``, ``p v``, ``do v^T``, ``p^T do``,
``ds k``, ``ds^T q``.  float32 whatever the dtype: the scores and their
scaling, the mask, the running maximum and sum, the exponentials, the
accumulator of ``o`` and its division by the row sums, ``lse``, ``delta =
rowsum(do * o)``, ``dp``, ``ds = p (dp - delta)``, the accumulators of
``dq``, ``dk``, ``dv`` and their scaling.  ``p`` and ``ds`` are cast to the
operands' dtype only as the left operand of the next product; ``o`` and the
three gradients are cast once, when they are written.  Where it differs
from the einsums: the probabilities are normalised after the second product
(``(p v) / l`` for ``softmax v``), and ``dp`` is not rounded to bfloat16 on
its way into ``ds`` as autodiff's is.
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


# ---------------------------------------------------------------------------
# The flash family: one forward and two backward kernels over q (b, heads,
# t_q, e_qk), k (b, kv_heads, t_k, e_qk), v (b, kv_heads, t_k, e_v), the
# grid (b, kv head, block of queries, block of keys) (the backward kernel of
# the keys: keys outside, queries inside).  A grid step holds the ``heads /
# kv_heads`` query heads its key-value head serves, one after another; the
# per-row statistics lse and delta travel as rows (b, heads, 1, t_q).
# ---------------------------------------------------------------------------
_A_B = (((1,), (0,)), ((), ()))              # a b
_A_BT = (((1,), (1,)), ((), ()))             # a b^T
LANES = 128
# blocks the kernels are offered, largest first: a grid step costs some
# 0.35 us whether it runs or is skipped, a 512 x 512 tile of scores 1-2 us
FLASH_BLOCKS = (1024, 512, 256, 128)
# v5e's scoped VMEM, which a kernel's blocks, scratch and tiles share
FLASH_VMEM_BYTES = 16 * 2 ** 20


def _dot(a, b, dims=_A_B):
    """A product of operands in their own dtype, accumulated in float32."""
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _flash_vmem_bytes(block_q, block_k, group, e_qk, e_v, itemsize):
    """VMEM of the hungriest of the three kernels at these blocks, reckoned
    from shapes: the blocks of q, do and their results and of k and v, each
    twice (the pipeline's two buffers) and as wide as whole lane tiles; the
    float32 accumulators and running statistics; one and a half float32
    tiles of scores, or three where the operands are float32 (Mosaic
    reuses the tiles' room from one query head of the step to the next).
    Held against what the chip's compiler took and refused at the cells'
    widths (``tests/test_ssd_kernel.py`` compiles both cells' shapes at the
    blocks this allows)."""
    lanes = lambda e: -(-e // LANES) * LANES
    wide = lanes(e_qk) + lanes(e_v)
    blocks = 2 * itemsize * (2 * group * block_q + 2 * block_k) * wide
    scratch = 4 * max(group * block_q, block_k) * (wide + 2 * LANES)
    return blocks + scratch + 3 * itemsize * block_q * block_k


def flash_tiles(t, heads, kv_heads, e_qk, e_v, dtype):
    """``(block_q, block_k)`` with which the kernels run causal attention of
    ``t`` positions over themselves, or None where they do not take it:
    bfloat16 or float32, a whole number of query heads a key-value head,
    widths that are multiples of 64 (half a lane tile: narrower heads leave
    most of the 128-wide array idle) and a length that is a multiple of 128.
    The blocks are of :data:`FLASH_BLOCKS`, divide the length and fit
    :data:`FLASH_VMEM_BYTES` by :func:`_flash_vmem_bytes`: the longest block
    of keys first (on a v5e the forward kernel gains more from it), then
    the longest block of queries.  A pure function of shapes and dtype: one
    shape traces one spelling."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return None
    if heads % kv_heads or e_qk % 64 or e_v % 64 or t % LANES:
        return None
    fits = lambda block_q, block_k: _flash_vmem_bytes(
        block_q, block_k, heads // kv_heads, e_qk, e_v,
        dtype.itemsize) <= FLASH_VMEM_BYTES
    divide = [block for block in FLASH_BLOCKS if t % block == 0]
    return next(((block_q, block_k) for block_k in divide
                 for block_q in divide if fits(block_q, block_k)), None)


def _seen(i, j, block_q, block_k, shape, q_axis, causal, t_q=None, t_k=None):
    """The mask of the tile (block ``i`` of the queries, ``j`` of the keys)
    of ``shape``, the queries along ``q_axis``: a query sees the keys up to
    its own position under ``causal``, and nothing past ``t_q`` or ``t_k``
    counts (given where the axis' last block is ragged)."""
    iota = jax.lax.broadcasted_iota
    qpos = i * block_q + iota(jnp.int32, shape, q_axis)
    kpos = j * block_k + iota(jnp.int32, shape, 1 - q_axis)
    terms = ([qpos >= kpos] if causal else []) \
        + ([qpos < t_q] if t_q else []) + ([kpos < t_k] if t_k else [])
    return functools.reduce(jnp.logical_and, terms)


def _as_row(col):
    """(n, 1) -> (1, n)."""
    return col.reshape(1, col.shape[0])


def _as_col(row):
    """(1, n) -> (n, 1)."""
    return row.reshape(row.shape[1], 1)


def _rows_before(x, first, limit):
    """``x`` with the rows from position ``limit`` on zeroed (``first`` the
    position of row 0): what the grid pads a ragged last block with need not
    be numbers, and 0 x NaN is NaN."""
    rows = first + jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], 1), 0)
    return jnp.where(rows < limit, x, jnp.zeros_like(x))


def _by_case(i, j, step, *, causal, ragged, block_q, block_k):
    """Run ``step(masked)`` for the tile (block ``i`` of the queries, ``j``
    of the keys): not at all where causality hides every key of it, with
    the mask where the diagonal crosses it or it is the ragged ``last``
    block of its axis (``ragged``: (index, last index) or None), without
    elsewhere."""
    from jax.experimental import pallas as pl

    crossed = j * block_k + block_k - 1 > i * block_q if causal else False
    if ragged is not None:
        crossed = crossed | (ragged[0] == ragged[1])
    if crossed is False:
        return step(False)
    runs = j * block_k <= i * block_q + block_q - 1 if causal else True
    pl.when(runs & crossed)(lambda: step(True))
    pl.when(runs & jnp.logical_not(crossed))(lambda: step(False))


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
               causal, scale, block_q, block_k, t_k):
    from jax.experimental import pallas as pl

    i, j = pl.program_id(2), pl.program_id(3)
    last = pl.num_programs(3) - 1
    group = q_ref.shape[1]
    ragged = t_k % block_k != 0

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(masked):
        k, v = k_ref[0, 0], v_ref[0, 0]              # (Bk, e_qk), (Bk, e_v)
        if masked:
            seen = _seen(i, j, block_q, block_k, (block_q, block_k), 0,
                         causal, t_k=ragged and t_k)
            if ragged:
                v = _rows_before(v, j * block_k, t_k)
        for g in range(group):
            s = _dot(q_ref[0, g], k, _A_BT) * scale  # (Bq, Bk) float32
            if masked:
                s = jnp.where(seen, s, _NEG_INF)
            # the mask is finite and key 0 is seen by every row in the
            # first block: no row's maximum stays at the mask's value
            m_prev = m_scr[g, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[g, :, :1] = l_scr[g, :, :1] * corr \
                + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[g] = acc_scr[g] * corr + _dot(p.astype(v.dtype), v, _A_B)
            m_scr[g, :, :1] = m_new

    _by_case(i, j, step, causal=causal, block_q=block_q, block_k=block_k,
             ragged=(j, last) if ragged else None)

    @pl.when(j == last)
    def _finish():
        for g in range(group):
            l = l_scr[g, :, :1]
            o_ref[0, g] = (acc_scr[g] / l).astype(o_ref.dtype)
            # the rows' logsumexp, for the backward kernels: m + log(l)
            lse_ref[0, g] = _as_row(m_scr[g, :, :1] + jnp.log(l))


def _fa_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                  dq_scr, *, causal, scale, block_q, block_k, t_k):
    from jax.experimental import pallas as pl

    i, j = pl.program_id(2), pl.program_id(3)
    last = pl.num_programs(3) - 1
    group = q_ref.shape[1]
    ragged = t_k % block_k != 0

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def step(masked):
        k, v = k_ref[0, 0], v_ref[0, 0]
        if masked:
            seen = _seen(i, j, block_q, block_k, (block_q, block_k), 0,
                         causal, t_k=ragged and t_k)
            if ragged:
                k = _rows_before(k, j * block_k, t_k)
                v = _rows_before(v, j * block_k, t_k)
        for g in range(group):
            s = _dot(q_ref[0, g], k, _A_BT) * scale
            p = jnp.exp(s - _as_col(lse_ref[0, g]))   # the probabilities
            if masked:
                p = jnp.where(seen, p, 0.0)
            dp = _dot(do_ref[0, g], v, _A_BT)
            ds = p * (dp - _as_col(delta_ref[0, g]))
            dq_scr[g] += _dot(ds.astype(k.dtype), k, _A_B)

    _by_case(i, j, step, causal=causal, block_q=block_q, block_k=block_k,
             ragged=(j, last) if ragged else None)

    @pl.when(j == last)
    def _finish():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _fa_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dk_ref, dv_ref, dk_scr, dv_scr, *, causal, scale,
                   block_q, block_k, t_q):
    """Every tile transposed, keys down and queries across: the rows' lse
    and delta are read as they are stored, and no product transposes its
    left operand."""
    from jax.experimental import pallas as pl

    j, i = pl.program_id(2), pl.program_id(3)
    last = pl.num_programs(3) - 1
    group = q_ref.shape[1]
    ragged = t_q % block_q != 0

    @pl.when(i == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def step(masked):
        k, v = k_ref[0, 0], v_ref[0, 0]
        if masked:
            seen = _seen(i, j, block_q, block_k, (block_k, block_q), 1,
                         causal, t_q=ragged and t_q)
        for g in range(group):
            q, do = q_ref[0, g], do_ref[0, g]
            if masked and ragged:
                q = _rows_before(q, i * block_q, t_q)
                do = _rows_before(do, i * block_q, t_q)
            s = _dot(k, q, _A_BT) * scale            # (Bk, Bq) float32
            p = jnp.exp(s - lse_ref[0, g])
            if masked:
                p = jnp.where(seen, p, 0.0)
            dv_scr[...] += _dot(p.astype(do.dtype), do, _A_B)
            dp = _dot(v, do, _A_BT)
            ds = p * (dp - delta_ref[0, g])
            if masked and ragged:
                ds = jnp.where(seen, ds, 0.0)        # delta's padding
            dk_scr[...] += _dot(ds.astype(q.dtype), q, _A_B)

    _by_case(i, j, step, causal=causal, block_q=block_q, block_k=block_k,
             ragged=(i, last) if ragged else None)

    @pl.when(i == last)
    def _finish():
        dk_ref[0, 0] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_specs(q, k, v, causal, block_q, block_k, keys_outside=False):
    """Blocks, grid and block specs of a call.  Under causality the steps
    that are skipped ask for the block the last step that ran held, so the
    pipeline fetches nothing for them."""
    from jax.experimental import pallas as pl

    b, heads, t_q, e_qk = q.shape
    _, kv_heads, t_k, e_v = v.shape
    group = heads // kv_heads
    block_q, block_k = min(block_q, t_q), min(block_k, t_k)
    n_q, n_k = pl.cdiv(t_q, block_q), pl.cdiv(t_k, block_k)
    if keys_outside:
        grid = (b, kv_heads, n_k, n_q)
        q_of = lambda j, i: jnp.maximum(i, j * block_k // block_q) \
            if causal else i
        k_of = lambda j, i: j
    else:
        grid = (b, kv_heads, n_q, n_k)
        q_of = lambda i, j: i
        k_of = lambda i, j: jnp.minimum(
            j, (i * block_q + block_q - 1) // block_k) if causal else j
    spec = lambda rows, width, of: pl.BlockSpec(
        (1, rows[0], rows[1], width),
        lambda n, h, x, y: (n, h, of(x, y), 0))
    specs = {"q": spec((group, block_q), e_qk, q_of),
             "o": spec((group, block_q), e_v, q_of),
             "k": spec((1, block_k), e_qk, k_of),
             "v": spec((1, block_k), e_v, k_of),
             "row": pl.BlockSpec((1, group, 1, block_q),
                                 lambda n, h, x, y: (n, h, 0, q_of(x, y)))}
    return block_q, block_k, grid, group, specs


def _flash_params():
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=(
        "parallel", "parallel", "parallel", "arbitrary"))


# jax traces and lowers every ``pallas_call`` call site on its own (0.7 s a
# site on a v5e's host, PERF.md section 7); as ``jit``s the sites of one
# shape share one trace and one lowered function
_STATIC = ("causal", "scale", "block_q", "block_k", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def flash_forward(q, k, v, *, causal, scale, block_q=128, block_k=128,
                  interpret=None):
    """``(o, lse)``: attention of q (b, heads, t_q, e_qk) over k (b,
    kv_heads, t_k, e_qk) and v (b, kv_heads, t_k, e_v), and the float32
    logsumexp of every row's scores, (b, heads, t_q)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, heads, t_q, _ = q.shape
    t_k, e_v = v.shape[2:]
    block_q, block_k, grid, group, specs = _flash_specs(
        q, k, v, causal, block_q, block_k)
    o, lse = pl.pallas_call(
        functools.partial(_fa_kernel, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k, t_k=t_k),
        out_shape=(_sds((b, heads, t_q, e_v), q.dtype, q),
                   _sds((b, heads, 1, t_q), jnp.float32, q)),
        grid=grid,
        in_specs=[specs["q"], specs["k"], specs["v"]],
        out_specs=(specs["o"], specs["row"]),
        scratch_shapes=[
            pltpu.VMEM((group, block_q, LANES), jnp.float32),  # running max
            pltpu.VMEM((group, block_q, LANES), jnp.float32),  # running sum
            pltpu.VMEM((group, block_q, e_v), jnp.float32),    # accumulator
        ],
        compiler_params=_flash_params(),
        name="_fa_kernel",
        interpret=resolve_interpret(interpret),
    )(q, k, v)
    return o, lse[:, :, 0]


@functools.partial(jax.jit, static_argnames=_STATIC)
def flash_backward(q, k, v, do, lse, delta, *, causal, scale, block_q=128,
                   block_k=128, interpret=None):
    """``(dq, dk, dv)`` from the forward pass's operands, the output's
    cotangent ``do`` (b, heads, t_q, e_v) and the rows' ``lse`` and
    ``delta = rowsum(do * o)`` (b, heads, t_q) float32: the probabilities
    are rebuilt a tile at a time in each of two kernels, one over the keys
    of a block of queries, one over the queries of a block of keys."""
    return (_flash_dq(q, k, v, do, lse, delta, causal, scale, block_q,
                      block_k, interpret),
            *_flash_dkv(q, k, v, do, lse, delta, causal, scale, block_q,
                        block_k, interpret))


def _flash_dq(q, k, v, do, lse, delta, causal, scale, block_q, block_k,
              interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    block_q, block_k, grid, group, specs = _flash_specs(
        q, k, v, causal, block_q, block_k)
    return pl.pallas_call(
        functools.partial(_fa_dq_kernel, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k,
                          t_k=k.shape[2]),
        out_shape=_sds(q.shape, q.dtype, q),
        grid=grid,
        in_specs=[specs[n] for n in ("q", "k", "v", "o", "row", "row")],
        out_specs=specs["q"],
        scratch_shapes=[pltpu.VMEM((group, block_q, q.shape[-1]),
                                   jnp.float32)],
        compiler_params=_flash_params(),
        name="_fa_dq_kernel",
        interpret=resolve_interpret(interpret),
    )(q, k, v, do, lse[:, :, None], delta[:, :, None])


def _flash_dkv(q, k, v, do, lse, delta, causal, scale, block_q, block_k,
               interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    block_q, block_k, grid, _, specs = _flash_specs(
        q, k, v, causal, block_q, block_k, keys_outside=True)
    return pl.pallas_call(
        functools.partial(_fa_dkv_kernel, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k,
                          t_q=q.shape[2]),
        out_shape=(_sds(k.shape, k.dtype, q), _sds(v.shape, v.dtype, q)),
        grid=grid,
        in_specs=[specs[n] for n in ("q", "k", "v", "o", "row", "row")],
        out_specs=(specs["k"], specs["v"]),
        scratch_shapes=[pltpu.VMEM((block_k, k.shape[-1]), jnp.float32),
                        pltpu.VMEM((block_k, v.shape[-1]), jnp.float32)],
        compiler_params=_flash_params(),
        name="_fa_dkv_kernel",
        interpret=resolve_interpret(interpret),
    )(q, k, v, do, lse[:, :, None], delta[:, :, None])


def flash_delta(o, do):
    """The softmax's part of the scores' gradient, ``rowsum(do * o)`` in
    float32 over the last axis."""
    return jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash_result(q, k, v, o, lse, causal, scale, blocks):
    """``o``, the forward kernel's result, as a function of ``q``, ``k``
    and ``v`` whose backward pass is the two kernels.  The forward kernel
    ran outside: its ``o`` and ``lse`` come in as arguments and are the
    residuals as they stand, so that a ``jax.checkpoint`` around the caller
    that keeps them (by name) does not run the forward kernel again to have
    them (:func:`flash_mha`)."""
    return o


def _flash_result_fwd(q, k, v, o, lse, causal, scale, blocks):
    return o, (q, k, v, o, lse)


def _flash_result_bwd(causal, scale, blocks, kept, do):
    q, k, v, o, lse = kept
    return flash_backward(q, k, v, do, lse, flash_delta(o, do),
                          causal=causal, scale=scale, block_q=blocks[0],
                          block_k=blocks[1]) + (None, None)


_flash_result.defvjp(_flash_result_fwd, _flash_result_bwd)


def flash_mha(q, k, v, causal, scale, blocks=(128, 128), kept=lambda a: a):
    """Differentiable attention through the kernels: q (b, heads, t_q,
    e_qk), k (b, kv_heads, t_k, e_qk), v (b, kv_heads, t_k, e_v) -> (b,
    heads, t_q, e_v), each key-value head serving ``heads / kv_heads`` query
    heads in order.  ``kept`` is applied to the two arrays the backward
    pass reads besides the operands (``o`` and ``lse``): a caller under a
    ``jax.checkpoint`` names them there for its policy to keep."""
    stop = jax.lax.stop_gradient
    o, lse = flash_forward(stop(q), stop(k), stop(v), causal=bool(causal),
                           scale=float(scale), block_q=blocks[0],
                           block_k=blocks[1])
    return _flash_result(q, k, v, kept(o), kept(lse), bool(causal),
                         float(scale), tuple(blocks))


# -- the family over (BH, T, D): ring attention's hops and the operator -------
def flash_forward_with_lse(q, k, v, causal, scale, interpret=None):
    """(out, lse) with lse (BH, T) f32 — building block for ring attention."""
    out, lse = flash_forward(q[:, None], k[:, None], v[:, None],
                             causal=causal, scale=scale, interpret=interpret)
    return out[:, 0], lse[:, 0]


def flash_dq(q, k, v, do, lse, delta, causal, scale, block_q=128,
             block_k=128, interpret=None):
    """dq for one (q-block × k-chunk) pairing; lse/delta are (BH, T) f32."""
    return _flash_dq(*(a[:, None] for a in (q, k, v, do, lse, delta)),
                     causal, scale, block_q, block_k, interpret)[:, 0]


def flash_dkv(q, k, v, do, lse, delta, causal, scale, block_q=128,
              block_k=128, interpret=None):
    """(dk, dv) for one (q-chunk × k-block) pairing."""
    dk, dv = _flash_dkv(*(a[:, None] for a in (q, k, v, do, lse, delta)),
                        causal, scale, block_q, block_k, interpret)
    return dk[:, 0], dv[:, 0]


def _flash_core(q, k, v, causal, scale):
    """Differentiable attention over (BH, T, D)."""
    return flash_mha(q[:, None], k[:, None], v[:, None], causal, scale)[:, 0]


@register("_contrib_flash_attention", arg_names=["query", "key", "value"],
          aliases=("flash_attention",))
def flash_attention(query, key, value, causal=False, scale=None):
    """Flash attention over (B, T, H, D) tensors (Pallas TPU kernel).

    Memory O(T) instead of O(T²); the per-(batch, head) score blocks live
    only in VMEM.  Works on any backend (interpret mode off-TPU)."""
    if scale is None:
        scale = query.shape[-1] ** -0.5
    heads_first = lambda x: x.transpose(0, 2, 1, 3)
    return heads_first(flash_mha(*map(heads_first, (query, key, value)),
                                 bool(causal), float(scale)))


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


def _flash_sizes(eqn):
    """(score pairs, e_qk, e_v, blocks of queries, blocks of keys) of a
    flash call: q (b, heads, t_q, e_qk), v (b, kv_heads, t_k, e_v); causal
    masking is not discounted (an upper bound)."""
    q, _, v = (a.aval for a in eqn.invars[:3])
    b, heads, t_q, e_qk = (int(x) for x in q.shape)
    t_k, e_v = (int(x) for x in v.shape[2:])
    grid = _grid_of(eqn)
    n_q, n_k = grid[2:] if len(grid) == 4 else (1, 1)
    return b * heads * t_q * t_k, e_qk, e_v, n_q, n_k


@_declare_cost("_fa_kernel")
def _cost_fa_fwd(eqn):
    q, k, v = (a.aval for a in eqn.invars[:3])
    pairs, e_qk, e_v, n_q, _ = _flash_sizes(eqn)
    return {
        "flops": 2 * pairs * (e_qk + e_v),            # q k^T and p v
        "transcendentals": pairs + pairs // int(v.shape[2]),  # exp, log
        # q resident across the inner k sweep; k/v re-fetched per q block
        "bytes_read": _nbytes(q) + n_q * (_nbytes(k) + _nbytes(v)),
        "bytes_written": _out_bytes(eqn),             # out + lse
    }


@_declare_cost("_fa_dq_kernel")
def _cost_fa_dq(eqn):
    q, k, v, do = (a.aval for a in eqn.invars[:4])
    pairs, e_qk, e_v, n_q, _ = _flash_sizes(eqn)
    rows = sum(_nbytes(a.aval) for a in eqn.invars[4:6])   # lse, delta
    return {
        "flops": 2 * pairs * (2 * e_qk + e_v),        # s, dp, ds k
        "transcendentals": pairs,                     # p recompute
        "bytes_read": _nbytes(q) + _nbytes(do) + rows
        + n_q * (_nbytes(k) + _nbytes(v)),
        "bytes_written": _out_bytes(eqn),             # dq
    }


@_declare_cost("_fa_dkv_kernel")
def _cost_fa_dkv(eqn):
    q, k, v, do = (a.aval for a in eqn.invars[:4])
    # the grid is keys outside, queries inside
    pairs, e_qk, e_v, n_k, _ = _flash_sizes(eqn)
    rows = sum(_nbytes(a.aval) for a in eqn.invars[4:6])
    return {
        "flops": 4 * pairs * (e_qk + e_v),            # s, dv, dp, dk
        "transcendentals": pairs,
        "bytes_read": _nbytes(k) + _nbytes(v)
        + n_k * (_nbytes(q) + _nbytes(do) + rows),
        "bytes_written": _out_bytes(eqn),             # dk + dv
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
