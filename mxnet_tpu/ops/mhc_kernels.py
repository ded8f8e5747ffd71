"""The manifold-constrained hyper-connection around a sublayer of
``transformer/mhc.py`` as two Pallas kernel pairs.

With the stream ``x`` held ``(tokens, n C)`` (``n`` streams of ``C``
columns; the view of ``(b, t, n, C)`` is free) and ``W = n^2 + 2n``::

    connect_in:   v = RMSNorm(x);  z = v phi  (W a token)
                  H_pre, H_post, H_res = the coefficients of z, alpha, bias
                  (sigmoids; Sinkhorn of exp(clamp(G_res)))
                  u = sum_j H_pre[j] x_j
    connect_out:  x'_i = sum_j H_res[i, j] x_j + H_post[i] y

Each is a ``jax.custom_vjp`` whose passes are kernels that stream the
``(tokens, n C)`` stream through VMEM the fewest times the mathematics
allows:

* :func:`connect_in` forward: ``_mhc_coeffs_fwd_kernel`` reads the stream
  once, over a grid (token block, column block of ``n C``) whose second
  axis is a reduction: the product with ``phi`` and the sum of squares are
  accumulated in VMEM, and on the last column block the norm's factor,
  ``alpha`` and ``bias``, the sigmoids, the clamp, ``exp`` and every
  Sinkhorn iteration run in float32 on the block's tokens, laid along the
  lanes.  ``_mhc_read_in_kernel`` reads it a second time and writes ``u``.
* :func:`connect_in` backward: ``_mhc_coeffs_bwd_kernel`` reads the stream
  and ``du`` once for ``dH_pre = sum_C du x_j``, then runs the coefficients'
  reverse mode on the block: Sinkhorn's forward again with the matrix
  before each of its ``2 x iterations`` divisions kept in VMEM scratch, and
  back through the same divisions (no implicit differentiation, no fewer
  iterations).  It writes the product's cotangent and the norm's term a
  token, and sums the gradients of ``alpha`` and ``bias`` over the token
  blocks.  ``_mhc_in_bwd_kernel`` reads the stream and ``du`` a second time
  and writes ``dx`` (the read-in's, the product's and the norm's terms
  summed in float32 and rounded once), summing ``x^T dz`` into ``phi``'s
  gradient over the token blocks.
* :func:`connect_out`: ``_mhc_out_fwd_kernel`` reads ``x`` and ``y`` once,
  ``_mhc_out_bwd_kernel`` reads ``dx'``, ``x`` and ``y`` once and in that
  pass writes ``dx`` and ``dy`` and reduces ``dH_res`` and ``dH_post``.
  Their grids run (token block, column block, stream) with the stream
  innermost: the ``n`` input streams are the same array handed ``n`` times
  with shifted index maps, whose blocks stay put while the stream a step
  writes changes.

**Layout.**  A block is (tokens, columns of one stream), so that ``n`` never
lies on the sublane axis; the coefficients of a token are one row of 128
float32 lanes (``H_pre`` at ``[0, n)``, ``H_post`` at ``[n, 2n)``,
``H_res[i, j]`` at ``2n + i n + j``), and ``z`` another with the norm's
factor at lane ``W``.  ``phi`` is handed with its ``W`` columns padded to
128.  Inside the coefficient kernels a block's rows are transposed so that
its tokens lie along the lanes.

**Precision** is the ``jax.numpy`` spelling's: the stream and ``u`` stay in
their dtype in HBM; the product with ``phi`` takes both in their dtype and
accumulates in float32; the norm, the coefficients, Sinkhorn and every mix
are float32, each result rounded once.  In the backward pass the product's
cotangent is rounded to the stream's dtype as the matrix unit's operand.

:func:`mhc_tiles` says which shapes the kernels take and at which blocks;
compile-or-interpret is ``pallas_kernels.resolve_interpret``'s.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..analysis.cost import declare_kernel_cost as _declare_cost
from .pallas_kernels import LANES, _dot, _nbytes, _out_bytes, _sds, \
    resolve_interpret
from .short_conv_kernels import _sigmoid

__all__ = ["mhc_tiles", "whole_blocks", "connect_in", "connect_out",
           "MAX_STREAMS"]

# tokens a grid step holds at most (a multiple of the lanes: the
# coefficient kernels lay a block's tokens along them)
ROW_BLOCK = 256
# columns of one stream a grid step holds at most
COLUMN_BLOCK = 2048
# streams the kernels take at most: W + 1 = n^2 + 2n + 1 lanes of a row
MAX_STREAMS = 8
# the scoped VMEM the kernels may use (of v5e's 128 MiB), and what
# ``_vmem_bytes`` may reckon a step's blocks at
VMEM_BYTES = 48 * 2 ** 20
VMEM_BUDGET = 32 * 2 ** 20
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _vmem_bytes(rows, columns, n, itemsize):
    """VMEM of the hungriest kernel, ``_mhc_out_bwd_kernel``, at these
    blocks, reckoned from shapes: ``n + 4`` blocks of the stream's dtype
    (``dx'``'s ``n``, ``x``, ``y``, ``dx``, ``dy``), each twice (the
    pipeline's two buffers), and ``n + 2`` float32 blocks of values.  Held
    against what the chip's compiler refused at four streams of 3,584 in
    bfloat16: 512 x 1,792 and 256 x 3,584 (51 MB here), where 256 x 1,792
    (26 MB) compiles."""
    return rows * columns * (2 * (n + 4) * itemsize + 4 * (n + 2))


def mhc_tiles(tokens, n, c, dtype):
    """``(tokens, columns)`` of a grid step where the kernels take the
    connection of ``tokens`` tokens of ``n`` streams ``c`` wide in
    ``dtype``; None where they do not (``c`` no multiple of 128, ``n``
    below 2 or above :data:`MAX_STREAMS`, a dtype other than bfloat16 or
    float32).  A pure function of shapes and dtype.  The tokens are
    :data:`ROW_BLOCK`, or fewer tokens rounded up to 128 (a count that is
    no multiple of the block is padded); the columns the largest multiple
    of 128 that divides ``c``, up to :data:`COLUMN_BLOCK`, whose blocks
    :func:`_vmem_bytes` reckons within :data:`VMEM_BUDGET`."""
    if c % LANES or not 2 <= n <= MAX_STREAMS:
        return None
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16),
                                jnp.dtype(jnp.float32)):
        return None
    rows = min(ROW_BLOCK, -(-tokens // LANES) * LANES)
    itemsize = jnp.dtype(dtype).itemsize
    columns = max(k for k in range(LANES, min(c, COLUMN_BLOCK) + 1, LANES)
                  if c % k == 0 and (k == LANES or _vmem_bytes(
                      rows, k, n, itemsize) <= VMEM_BUDGET))
    return rows, columns


def _width(n):
    return n * n + 2 * n


def _params(*semantics):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_BYTES)


def _pick(h, start, k, stride, n):
    """Lane ``start + k stride`` of ``h`` (rows, 128) as a column, ``k`` a
    traced index below ``n``."""
    col = h[:, start:start + 1]
    for m in range(1, n):
        lane = start + m * stride
        col = jnp.where(k == m, h[:, lane:lane + 1], col)
    return col


def _lanes(shape):
    return lax.broadcasted_iota(jnp.int32, shape, 1)


# -- the coefficients, a block's tokens along the lanes -----------------------
def _logits(zt, ab):
    """``alpha z + bias`` of every row: ``zt`` (128, rows), ``ab`` (128, 2)
    the bias and the ``alpha`` of each row."""
    return zt * ab[:, 1:2] + ab[:, 0:1]


def _res_rows(logits, n, clamp):
    """``exp(clamp(G_res))`` as ``n`` arrays (n, rows): row ``i`` of every
    token's matrix, its columns ``j`` on the sublanes."""
    low, high = clamp
    return [jnp.exp(jnp.clip(logits[2 * n + i * n:2 * n + (i + 1) * n],
                             low, high)) for i in range(n)]


def _sinkhorn(rows, iters, eps, keep_scr=None):
    """``mhc.sinkhorn`` of the matrices whose rows are ``rows``; with
    ``keep_scr``, the matrix before each division is written to it, rows
    ``(2 k + side) n + i``."""
    n = len(rows)

    def iteration(k, rows):
        if keep_scr is not None:
            for i in range(n):
                keep_scr[2 * k * n + i] = rows[i]
        cols = functools.reduce(jnp.add, rows) + eps
        rows = [r / cols for r in rows]
        if keep_scr is not None:
            for i in range(n):
                keep_scr[(2 * k + 1) * n + i] = rows[i]
        return tuple(r / (jnp.sum(r, axis=0, keepdims=True) + eps)
                     for r in rows)

    return list(lax.fori_loop(0, iters, iteration, tuple(rows)))


def _sinkhorn_bwd(g, iters, eps, keep_scr):
    """The cotangent of Sinkhorn's input from that of its output ``g`` (n
    arrays (n, rows)), back through the divisions ``keep_scr`` holds the
    dividends of."""
    n = len(g)

    def iteration(back, g):
        k = iters - 1 - back
        # rows: out_i = a_i / e_i, e_i = sum_j a_i[j] + eps
        a = [keep_scr[(2 * k + 1) * n + i] for i in range(n)]
        e = [jnp.sum(r, axis=0, keepdims=True) + eps for r in a]
        g = [gi / ei - jnp.sum(gi * ai, axis=0, keepdims=True) / (ei * ei)
             for gi, ai, ei in zip(g, a, e)]
        # columns: out_i = b_i / d, d = sum_i b_i + eps
        b = [keep_scr[2 * k * n + i] for i in range(n)]
        d = functools.reduce(jnp.add, b) + eps
        s = functools.reduce(jnp.add, [gi * bi for gi, bi in zip(g, b)])
        return tuple(gi / d - s / (d * d) for gi in g)

    return list(lax.fori_loop(0, iters, iteration, tuple(g)))


def _clip_slope(logits, clamp):
    """``jnp.clip``'s derivative: 1 inside, 0 outside, one half at a bound
    (``lax.max`` and ``lax.min`` split a tie)."""
    low, high = clamp
    inside = jnp.where((logits > low) & (logits < high), 1.0, 0.0)
    return jnp.where((logits == low) | (logits == high), 0.5, inside)


def _mhc_coeffs_fwd_kernel(x_ref, phi_ref, ab_ref, h_ref, z_ref, acc_scr,
                           ss_scr, t_scr, *, n, total, norm_eps, clamp,
                           iters, eps):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    k = pl.program_id(1)
    width = _width(n)

    @pl.when(k == 0)
    def _first_columns():
        acc_scr[...] = jnp.zeros(acc_scr.shape, f32)
        ss_scr[...] = jnp.zeros(ss_scr.shape, f32)

    x = x_ref[...]
    acc_scr[...] += _dot(x, phi_ref[...])
    xf = x.astype(f32)
    ss_scr[...] += jnp.sum(xf * xf, axis=1, keepdims=True)

    @pl.when(k == pl.num_programs(1) - 1)
    def _coefficients():
        r = lax.rsqrt(ss_scr[...] / total + norm_eps)
        z = acc_scr[...] * r
        z_ref[...] = jnp.where(_lanes(z.shape) == width, r, z)
        logits = _logits(z.T, ab_ref[...])
        t_scr[...] = jnp.zeros(t_scr.shape, f32)
        t_scr[0:n] = _sigmoid(logits[0:n])
        t_scr[n:2 * n] = 2.0 * _sigmoid(logits[n:2 * n])
        rows = _sinkhorn(_res_rows(logits, n, clamp), iters, eps)
        for i in range(n):
            t_scr[2 * n + i * n:2 * n + (i + 1) * n] = rows[i]
        h_ref[...] = t_scr[...].T


def _mhc_read_in_kernel(*refs, n):
    f32 = jnp.float32
    x_refs, h_ref, u_ref = refs[:n], refs[n], refs[n + 1]
    h = h_ref[...]
    u = h[:, 0:1] * x_refs[0][...].astype(f32)
    for j in range(1, n):
        u = u + h[:, j:j + 1] * x_refs[j][...].astype(f32)
    u_ref[...] = u.astype(u_ref.dtype)


def _mhc_coeffs_bwd_kernel(*refs, n, total, clamp, iters, eps):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    x_refs = refs[:n]
    du_ref, z_ref, dh_ref, ab_ref, dz_ref, dab_ref, acc_scr, t_scr, \
        keep_scr = refs[n:]
    width = _width(n)

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _first_block():
        dab_ref[...] = jnp.zeros(dab_ref.shape, f32)

    @pl.when(pl.program_id(1) == 0)
    def _first_columns():
        acc_scr[...] = jnp.zeros(acc_scr.shape, f32)

    du = du_ref[...].astype(f32)
    for j in range(n):
        acc_scr[:, j:j + 1] += jnp.sum(du * x_refs[j][...].astype(f32),
                                       axis=1, keepdims=True)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _coefficients_reverse():
        gt = (acc_scr[...] + dh_ref[...]).T
        zt = z_ref[...].T
        ab = ab_ref[...]
        logits = _logits(zt, ab)
        t_scr[...] = jnp.zeros(t_scr.shape, f32)
        s = _sigmoid(logits[0:n])
        t_scr[0:n] = gt[0:n] * s * (1.0 - s)
        s = _sigmoid(logits[n:2 * n])
        t_scr[n:2 * n] = gt[n:2 * n] * 2.0 * s * (1.0 - s)
        e = _res_rows(logits, n, clamp)
        _sinkhorn(e, iters, eps, keep_scr)
        g = _sinkhorn_bwd([gt[2 * n + i * n:2 * n + (i + 1) * n]
                           for i in range(n)], iters, eps, keep_scr)
        for i in range(n):
            lo, hi = 2 * n + i * n, 2 * n + (i + 1) * n
            t_scr[lo:hi] = g[i] * e[i] * _clip_slope(logits[lo:hi], clamp)
        dlogits = t_scr[...]
        dab_ref[:, 0:1] += jnp.sum(dlogits, axis=1, keepdims=True)
        dab_ref[:, 1:2] += jnp.sum(dlogits * zt, axis=1, keepdims=True)
        # z = P r, r = (sum x^2 / total + eps)^-1/2: the product's
        # cotangent dz r, and the stream's term from r, -dr r^3 / total x
        dz = dlogits * ab[:, 1:2]
        r = zt[width:width + 1]
        dr = jnp.sum(dz * zt, axis=0, keepdims=True) / r
        t_scr[...] = dz * r
        t_scr[width:width + 1] = -dr * r * r * r / total
        dz_ref[...] = t_scr[...].T


def _mhc_in_bwd_kernel(x_ref, du_ref, phi_ref, h_ref, dz_ref, dt_ref,
                       dx_ref, dphi_ref, *, n, blocks):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    width = _width(n)

    @pl.when(pl.program_id(1) == 0)
    def _first_tokens():
        dphi_ref[...] = jnp.zeros(dphi_ref.shape, f32)

    x = x_ref[...]
    dz = dz_ref[...]
    # lane W of dz is the norm's factor of x: phi's column W is zero
    dp = dz.astype(x.dtype)
    h_pre = _pick(h_ref[...], 0, pl.program_id(0) // blocks, 1, n)
    dx = _dot(dp, phi_ref[...], _NT) + dz[:, width:width + 1] \
        * x.astype(f32) + h_pre * du_ref[...].astype(f32) \
        + dt_ref[...].astype(f32)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    dphi_ref[...] += _dot(x, dp, _TN)


def _mhc_out_fwd_kernel(*refs, n):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    x_refs, y_ref, h_ref, o_ref = refs[:n], refs[n], refs[n + 1], \
        refs[n + 2]
    i = pl.program_id(2)
    h = h_ref[...]
    s = _pick(h, n, i, 1, n) * y_ref[...].astype(f32)
    for j in range(n):
        s = s + _pick(h, 2 * n + j, i, n, n) * x_refs[j][...].astype(f32)
    o_ref[...] = s.astype(o_ref.dtype)


def _mhc_out_bwd_kernel(*refs, n):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    g_refs = refs[:n]
    x_ref, y_ref, h_ref, dx_ref, dy_ref, dh_ref = refs[n:]
    j = pl.program_id(2)
    first = j == 0

    @pl.when((pl.program_id(1) == 0) & first)
    def _first_columns():
        dh_ref[...] = jnp.zeros(dh_ref.shape, f32)

    h = h_ref[...]
    g = [r[...].astype(f32) for r in g_refs]
    x = x_ref[...].astype(f32)
    dx = _pick(h, 2 * n, j, 1, n) * g[0]
    for i in range(1, n):
        dx = dx + _pick(h, 2 * n + i * n, j, 1, n) * g[i]
    dx_ref[...] = dx.astype(dx_ref.dtype)
    lanes = _lanes(dh_ref.shape)
    dh = dh_ref[...]
    for i in range(n):
        dh = dh + jnp.where(lanes == 2 * n + i * n + j,
                            jnp.sum(g[i] * x, axis=1, keepdims=True), 0.0)
    dh_ref[...] = dh

    @pl.when(first)
    def _the_sublayers_result():
        y = y_ref[...].astype(f32)
        dy = h[:, n:n + 1] * g[0]
        for i in range(1, n):
            dy = dy + h[:, n + i:n + i + 1] * g[i]
        dy_ref[...] = dy.astype(dy_ref.dtype)
        for i in range(n):
            dh_ref[:, n + i:n + i + 1] += jnp.sum(g[i] * y, axis=1,
                                                  keepdims=True)


# -- the calls ---------------------------------------------------------------
def _estimate(flops, transcendentals, operands, results):
    """``pl.CostEstimate`` of a call: ``bytes_accessed`` one pass over each
    distinct operand and each result."""
    from jax.experimental import pallas as pl

    distinct = {id(a): a for a in operands}.values()
    return pl.CostEstimate(
        flops=int(flops), transcendentals=int(transcendentals),
        bytes_accessed=sum(a.size * a.dtype.itemsize
                           for a in list(distinct) + list(results)))


def _sinkhorn_ops(n, iters):
    """Operations of Sinkhorn on one token: two sums and two divisions of
    ``n^2`` an iteration."""
    return 4 * n * n * iters


def _scoped(name, call):
    """``call`` whose operations sit under the program scope ``name``."""
    def run(*args):
        with jax.named_scope(name):
            return call(*args)
    return run


def _stream_specs(n, rows, columns, blocks, index):
    """``n`` block specs over the same ``(tokens, n C)`` array, stream
    ``j``'s block at ``index(j, *grid)``."""
    from jax.experimental import pallas as pl

    return [pl.BlockSpec((rows, columns), functools.partial(index, j))
            for j in range(n)]


@functools.partial(jax.jit, static_argnames=("tiles", "n", "settings"))
def _in_forward(x, phi, ab, *, tiles, n, settings):
    """x (tokens, n C), whole blocks of ``tiles[0]`` tokens; phi (n C, 128);
    ab (128, 2).  Returns u (tokens, C), h and z (tokens, 128) float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, columns = tiles
    tokens, wide = x.shape
    c = wide // n
    blocks = c // columns
    norm_eps, clamp, iters, eps = settings
    f32 = jnp.float32
    width = _width(n)
    row = pl.BlockSpec((rows, LANES), lambda t, k: (t, 0))
    h_shape = _sds((tokens, LANES), f32, x)
    h, z = _scoped("hc_coeffs", pl.pallas_call(
        functools.partial(_mhc_coeffs_fwd_kernel, n=n, total=wide,
                          norm_eps=norm_eps, clamp=clamp, iters=iters,
                          eps=eps),
        out_shape=[h_shape, h_shape],
        grid=(tokens // rows, wide // columns),
        in_specs=[pl.BlockSpec((rows, columns), lambda t, k: (t, k)),
                  pl.BlockSpec((columns, LANES), lambda t, k: (k, 0)),
                  pl.BlockSpec((LANES, 2), lambda t, k: (0, 0))],
        out_specs=[row, row],
        scratch_shapes=[pltpu.VMEM((rows, LANES), f32),
                        pltpu.VMEM((rows, 1), f32),
                        pltpu.VMEM((LANES, rows), f32)],
        compiler_params=_params("parallel", "arbitrary"),
        cost_estimate=_estimate(
            tokens * (2 * wide * width + 3 * wide
                      + _sinkhorn_ops(n, iters) + 4 * width),
            tokens * (n * n + 2 * n + 1), (x, phi, ab), (h_shape, h_shape)),
        name="_mhc_coeffs_fwd_kernel",
        interpret=resolve_interpret(),
    ))(x, phi, ab)
    u_shape = _sds((tokens, c), x.dtype, x)
    u = _scoped("hc_read_in", pl.pallas_call(
        functools.partial(_mhc_read_in_kernel, n=n),
        out_shape=u_shape,
        grid=(tokens // rows, blocks),
        in_specs=_stream_specs(n, rows, columns, blocks,
                               lambda j, t, k: (t, j * blocks + k))
        + [pl.BlockSpec((rows, LANES), lambda t, k: (t, 0))],
        out_specs=pl.BlockSpec((rows, columns), lambda t, k: (t, k)),
        compiler_params=_params("parallel", "parallel"),
        cost_estimate=_estimate(2 * n * tokens * c, 0, (x, h),
                                (u_shape,)),
        name="_mhc_read_in_kernel",
        interpret=resolve_interpret(),
    ))(*([x] * n), h)
    return u, h, z


@functools.partial(jax.jit, static_argnames=("tiles", "n", "settings"))
def _in_backward(x, phi, ab, h, z, du, dh, dt, *, tiles, n, settings):
    """The gradients of :func:`_in_forward`'s ``u`` and ``h``, and of the
    stream passed through (``dt``), by x, phi (float32) and ab."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, columns = tiles
    tokens, wide = x.shape
    c = wide // n
    blocks = c // columns
    _, clamp, iters, eps = settings
    f32 = jnp.float32
    width = _width(n)
    row = pl.BlockSpec((rows, LANES), lambda t, k: (t, 0))
    dz_shape = _sds((tokens, LANES), f32, x)
    dab_shape = _sds((LANES, 2), f32, x)
    dz, dab = _scoped("hc_coeffs", pl.pallas_call(
        functools.partial(_mhc_coeffs_bwd_kernel, n=n, total=wide,
                          clamp=clamp, iters=iters, eps=eps),
        out_shape=[dz_shape, dab_shape],
        grid=(tokens // rows, blocks),
        in_specs=_stream_specs(n, rows, columns, blocks,
                               lambda j, t, k: (t, j * blocks + k))
        + [pl.BlockSpec((rows, columns), lambda t, k: (t, k)), row, row,
           pl.BlockSpec((LANES, 2), lambda t, k: (0, 0))],
        out_specs=[row, pl.BlockSpec((LANES, 2), lambda t, k: (0, 0))],
        scratch_shapes=[pltpu.VMEM((rows, LANES), f32),
                        pltpu.VMEM((LANES, rows), f32),
                        pltpu.VMEM((2 * iters * n, n, rows), f32)],
        compiler_params=_params("arbitrary", "arbitrary"),
        cost_estimate=_estimate(
            tokens * (2 * wide + 3 * _sinkhorn_ops(n, iters) + 12 * width),
            tokens * (n * n + 2 * n), (x, du, z, dh, ab),
            (dz_shape, dab_shape)),
        name="_mhc_coeffs_bwd_kernel",
        interpret=resolve_interpret(),
    ))(*([x] * n), du, z, dh, ab)
    dx_shape = _sds(x.shape, x.dtype, x)
    dphi_shape = _sds((wide, LANES), f32, x)
    dx, dphi = _scoped("hc_read_in", pl.pallas_call(
        functools.partial(_mhc_in_bwd_kernel, n=n, blocks=blocks),
        out_shape=[dx_shape, dphi_shape],
        grid=(wide // columns, tokens // rows),
        in_specs=[pl.BlockSpec((rows, columns), lambda k, t: (t, k)),
                  pl.BlockSpec((rows, columns),
                               lambda k, t: (t, k % blocks)),
                  pl.BlockSpec((columns, LANES), lambda k, t: (k, 0)),
                  pl.BlockSpec((rows, LANES), lambda k, t: (t, 0)),
                  pl.BlockSpec((rows, LANES), lambda k, t: (t, 0)),
                  pl.BlockSpec((rows, columns), lambda k, t: (t, k))],
        out_specs=[pl.BlockSpec((rows, columns), lambda k, t: (t, k)),
                   pl.BlockSpec((columns, LANES), lambda k, t: (k, 0))],
        compiler_params=_params("parallel", "arbitrary"),
        cost_estimate=_estimate(tokens * wide * (4 * width + 5), 0,
                                (x, du, phi, h, dz, dt),
                                (dx_shape, dphi_shape)),
        name="_mhc_in_bwd_kernel",
        interpret=resolve_interpret(),
    ))(x, du, phi, h, dz, dt)
    return dx, dphi, dab


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _connect_in(x, phi, ab, tiles, n, settings):
    u, h, _ = _in_forward(x, phi, ab, tiles=tiles, n=n, settings=settings)
    return u, h, x


def _connect_in_fwd(x, phi, ab, tiles, n, settings):
    u, h, z = _in_forward(x, phi, ab, tiles=tiles, n=n, settings=settings)
    return (u, h, x), (x, phi, ab, h, z)


def _connect_in_bwd(tiles, n, settings, kept, grads):
    x, phi, ab, h, z = kept
    du, dh, dt = grads
    dx, dphi, dab = _in_backward(x, phi, ab, h, z, du.astype(x.dtype),
                                 dh.astype(jnp.float32), dt.astype(x.dtype),
                                 tiles=tiles, n=n, settings=settings)
    return dx, dphi.astype(phi.dtype), dab.astype(ab.dtype)


_connect_in.defvjp(_connect_in_fwd, _connect_in_bwd)


def whole_blocks(a, tiles):
    """``a`` (t, ...) with zero rows after its ``t`` up to a whole number of
    ``tiles``' token blocks: what :func:`connect_in` and
    :func:`connect_out` take.  A padded row's coefficients are the biases'
    and its cotangents zero."""
    t = a.shape[0]
    rows = -(-t // tiles[0]) * tiles[0]
    if rows == t:
        return a
    return jnp.pad(a, [(0, rows - t)] + [(0, 0)] * (a.ndim - 1))


def connect_in(x, phi, bias, alpha, tiles, settings):
    """``u`` (tokens, C) in ``x``'s dtype, the coefficients ``h`` (tokens,
    128) float32 (the module's docstring) and ``x`` itself, of the stream
    ``x`` (tokens, n C), whole blocks of the ``tiles`` :func:`mhc_tiles`
    gave.  ``phi`` (n C, W), ``bias`` (W), ``alpha`` (3) are a
    connection's leaves; ``settings`` is ``(norm_eps, (low, high) of the
    clamp, Sinkhorn's iterations, its eps)``.  Differentiable in ``x``,
    ``phi``, ``bias`` and ``alpha``.  Hand :func:`connect_out` the ``x``
    returned: its cotangent then joins ``dx`` inside the backward kernel,
    where autodiff would add the two in a pass of its own."""
    f32 = jnp.float32
    n = round((phi.shape[1] + 1) ** 0.5) - 1       # W + 1 = (n + 1)^2
    width = _width(n)
    extra = LANES - width
    by_row = alpha.astype(f32)[np.array([0] * n + [1] * n + [2] * n * n)]
    ab = jnp.stack([jnp.pad(bias.astype(f32), (0, extra)),
                    jnp.pad(by_row, (0, extra))], axis=1)
    return _connect_in(x, jnp.pad(phi, ((0, 0), (0, extra))), ab, tiles, n,
                       settings)


@functools.partial(jax.jit, static_argnames=("tiles", "n"))
def _out_forward(x, y, h, *, tiles, n):
    """x (tokens, n C), y (tokens, C), h (tokens, 128): x' (tokens, n C)."""
    from jax.experimental import pallas as pl

    rows, columns = tiles
    tokens, wide = x.shape
    c = wide // n
    blocks = c // columns
    out_shape = _sds(x.shape, x.dtype, x)
    return pl.pallas_call(
        functools.partial(_mhc_out_fwd_kernel, n=n),
        out_shape=out_shape,
        grid=(tokens // rows, blocks, n),
        in_specs=_stream_specs(n, rows, columns, blocks,
                               lambda j, t, k, i: (t, j * blocks + k))
        + [pl.BlockSpec((rows, columns), lambda t, k, i: (t, k)),
           pl.BlockSpec((rows, LANES), lambda t, k, i: (t, 0))],
        out_specs=pl.BlockSpec((rows, columns),
                               lambda t, k, i: (t, i * blocks + k)),
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        cost_estimate=_estimate(2 * (n + 1) * tokens * wide, 0, (x, y, h),
                                (out_shape,)),
        name="_mhc_out_fwd_kernel",
        interpret=resolve_interpret(),
    )(*([x] * n), y, h)


@functools.partial(jax.jit, static_argnames=("tiles", "n"))
def _out_backward(x, y, h, g, *, tiles, n):
    """The gradients of :func:`_out_forward` by x, y and h (float32) from
    that of its result, ``g``."""
    from jax.experimental import pallas as pl

    rows, columns = tiles
    tokens, wide = x.shape
    c = wide // n
    blocks = c // columns
    shapes = [_sds(x.shape, x.dtype, x), _sds(y.shape, y.dtype, x),
              _sds((tokens, LANES), jnp.float32, x)]
    return pl.pallas_call(
        functools.partial(_mhc_out_bwd_kernel, n=n),
        out_shape=shapes,
        grid=(tokens // rows, blocks, n),
        in_specs=_stream_specs(n, rows, columns, blocks,
                               lambda i, t, k, j: (t, i * blocks + k))
        + [pl.BlockSpec((rows, columns), lambda t, k, j: (t, j * blocks + k)),
           pl.BlockSpec((rows, columns), lambda t, k, j: (t, k)),
           pl.BlockSpec((rows, LANES), lambda t, k, j: (t, 0))],
        out_specs=[
            pl.BlockSpec((rows, columns), lambda t, k, j: (t, j * blocks + k)),
            pl.BlockSpec((rows, columns), lambda t, k, j: (t, k)),
            pl.BlockSpec((rows, LANES), lambda t, k, j: (t, 0))],
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        cost_estimate=_estimate(
            2 * tokens * c * (n * n + n * n + n + n), 0, (g, x, y, h),
            shapes),
        name="_mhc_out_bwd_kernel",
        interpret=resolve_interpret(),
    )(*([g] * n), x, y, h)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _connect_out(x, y, h, tiles, n):
    return _out_forward(x, y, h, tiles=tiles, n=n)


def _connect_out_fwd(x, y, h, tiles, n):
    return _out_forward(x, y, h, tiles=tiles, n=n), (x, y, h)


def _connect_out_bwd(tiles, n, kept, g):
    x, y, h = kept
    return tuple(_out_backward(x, y, h, g.astype(x.dtype), tiles=tiles,
                               n=n))


_connect_out.defvjp(_connect_out_fwd, _connect_out_bwd)


def connect_out(x, y, h, tiles):
    """The stream after the sublayer, ``x'`` (tokens, n C) in ``x``'s dtype,
    from the stream ``x`` (tokens, n C) :func:`connect_in` returned, the
    sublayer's result ``y`` (tokens, C) and the coefficients ``h`` it gave,
    whole blocks of the ``tiles`` :func:`mhc_tiles` gave.  Differentiable
    in all three."""
    return _connect_out(x, y.astype(x.dtype), h, tiles,
                        x.shape[1] // y.shape[1])


# declared costs (analysis/cost.py KERNEL_COSTS; docs/fusion.md): what each
# call's ``pl.CostEstimate`` says, and one pass over its distinct operands
# and its results
def _declared(eqn):
    estimate = eqn.params["cost_estimate"]
    operands = {id(v): v for v in eqn.invars}.values()
    return {"flops": estimate.flops,
            "transcendentals": estimate.transcendentals,
            "bytes_read": sum(_nbytes(v.aval) for v in operands),
            "bytes_written": _out_bytes(eqn)}


for _name in ("_mhc_coeffs_fwd_kernel", "_mhc_read_in_kernel",
              "_mhc_coeffs_bwd_kernel", "_mhc_in_bwd_kernel",
              "_mhc_out_fwd_kernel", "_mhc_out_bwd_kernel"):
    _declare_cost(_name)(_declared)
