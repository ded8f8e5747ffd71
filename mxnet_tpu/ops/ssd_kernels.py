"""The Mamba-2 chunked scan as a Pallas kernel pair.

``transformer/ssm.py::ssd_chunked`` computes the state-space recurrence a
chunk of ``L`` steps at a time; for every head, with ``xdt = x dt`` and
``cum`` the log-decays summed from the chunk's start::

    y[i] = sum_{j<=i} exp(cum[i] - cum[j]) (C[i] . B[j]) xdt[j]   within
         + exp(cum[i]) C[i] . state                 the state handed over
    state' = exp(cum[L-1]) state + sum_j exp(cum[L-1] - cum[j]) xdt[j] B[j]^T

Spelled in ``jax.numpy`` the first line is three arrays of ``L x L`` a
(chunk, head) pair (``C B^T``, the decay matrix, their masked product) and,
under autodiff, a gradient for each; the other two are products over
``(L, h, p)`` and ``(h, p, n)`` arrays.  On the TPU XLA fuses most of the
``L x L`` work into the products, but lays the arrays between them out each
its own way: at ``granite-4.0-h-micro``'s sizes two thirds of the scan's time
are 67 MB layout copies and converts (``PERF.md`` §6, PR 33).
:func:`ssd_scan` is the same map behind a ``jax.custom_vjp`` whose forward
and backward are one kernel each, over the grid (sequence, chunk, step of
8 heads): the chunks of a sequence in order, the states (and in the backward
pass their gradients, the chunks from last to first) carried in VMEM scratch,
every ``L x L`` array in VMEM only.  The residuals are the five inputs and
the state each chunk was handed (float32, ``(h p, n)`` a chunk).

**Time is the last axis of every operand** (``x^T`` (b, h p, t), ``B^T``,
``C^T`` (b, n, t), ``dt^T``, ``cum^T`` (b, h, t)): the layout XLA itself
gives this program's activations on the TPU, so the wrapper's transposes are
relabelings there, a head's rows are whole sublane tiles (no lane is shifted
or masked), and what differs by head scales rows.  Forward, a (chunk, head)
tile: ``S^T = B C^T`` (once a chunk, kept in scratch across the chunk's
heads), ``D^T = exp(where(i >= j, cum_i - cum_j, -inf))``, ``M^T = (S^T *
D^T)`` cast to the compute dtype, ``y^T = xdt^T M^T + exp(cum) (state
C^T)`` cast once, and the state's update.  Backward, with ``S``, ``D``, ``M``
rebuilt from the inputs: ``dxdt = M^T dy + to_end (B dstate'^T)``; ``dM = dy
xdt^T``; ``dS = sum_h dM * D`` (the heads share ``B`` and ``C``: summed in
scratch, the head axis the grid's inner one); ``dC = dS B + sum_h (exp(cum)
dy) state``, ``dB = dS^T C + sum_h (to_end xdt) dstate'``; ``dstate =
exp(cum[L-1]) dstate' + (exp(cum) dy)^T C``; ``dx = dxdt dt``, ``ddt = sum_p
dxdt x``.  The gradient of ``cum[i]`` as row ``i`` of the decays is ``dy_i .
y_i`` and as column ``i`` of the chunk's own ``-xdt_i . (M^T dy)_i`` (``sum_j
(dM * M)_ij`` and ``sum_j (dM * M)_ji``: two sums over a head's width, no
``L x L`` reduction; ``y`` is rebuilt for it), besides its part in
``to_end``; ``cum[L-1]`` also takes what ``to_end`` and the kept share of
the state give.

What is float32 whatever the compute dtype: ``dt``, ``cum``, the differences
and their exponentials, ``S``, the carried state and its gradient, the
accumulation of every product, ``ddt`` and ``dcum``.  The operands of the
products (``B``, ``C``, ``xdt``, ``M``, the state as ``C`` reads it, ``xdt
to_end``, and in the backward pass ``dy``, ``dS`` and ``dstate'``) are in
the compute dtype, as XLA's own products of the einsum spelling have them.

:func:`tiles` is the rule that says which shapes the kernels take;
compile-or-interpret is ``pallas_kernels.resolve_interpret``'s.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..analysis.cost import declare_kernel_cost as _declare_cost
from .pallas_kernels import (LANES, _A_BT, _dot, _nbytes, _out_bytes, _sds,
                             resolve_interpret)

__all__ = ["ssd_scan", "tiles", "heads_per_step"]

# heads a grid step holds: a sublane tile of the (heads, time) decays
STEP_HEADS = 8

_AT_B = (((0,), (0,)), ((), ()))            # a^T b


def tiles(chunk, heads, head_dim, state, dtype):
    """Whether the kernels take a scan of these shapes: chunk and state
    multiples of 128 (the ``L x L`` and ``N x L`` tiles), heads of 64 or
    128 columns, in eights or at most eight of them filling whole 128-row
    tiles, bfloat16 or float32 operands.  A pure function of shapes and
    dtype: one shape traces one spelling."""
    if chunk % LANES or state % LANES or head_dim not in (64, 128):
        return False
    if heads % STEP_HEADS and (heads > STEP_HEADS
                               or heads * head_dim % LANES):
        return False
    return jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                jnp.dtype(jnp.float32))


def heads_per_step(heads):
    """Heads a grid step holds: :data:`STEP_HEADS`, or all of fewer."""
    return heads if heads % STEP_HEADS else STEP_HEADS


def _slabs(heads, p):
    """A step's ``heads * p`` rows in slabs of whole 128-row tiles:
    ``[(first row, rows, [(head, from, to)])]``, ``from``/``to`` the
    head's rows within the slab."""
    per = max(1, LANES // p)
    return [(s * per * p, per * p,
             [(s * per + j, j * p, (j + 1) * p) for j in range(per)])
            for s in range(heads // per)]


def _by_head(members, shape, value):
    """An array of ``shape`` whose rows of each head hold ``value(k)``, a
    row (or one element) broadcast over them."""
    out = None
    for k, _, to in reversed(members):
        rows = jnp.broadcast_to(value(k), shape)
        out = rows if out is None else jnp.where(
            lax.broadcasted_iota(jnp.int32, shape, 0) < to, rows, out)
    return out


def _decay_t(ccol_ref, cum, k):
    """The transposed decay matrix of the step's head ``k``: ``exp(cum_i -
    cum_j)`` at row ``j``, column ``i`` for ``i >= j``, 0 below the
    diagonal; masked before the exponential."""
    diff = cum[k:k + 1, :] - ccol_ref[0, 0, :, k:k + 1]
    row = lax.broadcasted_iota(jnp.int32, diff.shape, 0)
    col = lax.broadcasted_iota(jnp.int32, diff.shape, 1)
    return jnp.exp(jnp.where(col >= row, diff, -jnp.inf))


def _chunk_decays(cum):
    """Of the step's heads, a row each: ``exp(cum_i)``, what step ``i``
    keeps of the state the chunk was handed; ``exp(cum_last - cum_i)``, what
    the chunk's end keeps of step ``i``; and ``exp(cum_last)``."""
    last = cum[:, cum.shape[1] - 1:]
    return jnp.exp(cum), jnp.exp(last - cum), jnp.exp(last)


def _slab_rows(members, shape, dt_ref, decays):
    """Over a slab of ``shape`` (rows, L), each head's rows holding its own:
    ``dt``, the first two of :func:`_chunk_decays`, and the third over
    (rows, 1)."""
    start, end, whole = decays
    return (_by_head(members, shape, lambda k: dt_ref[0, k:k + 1, :]),
            _by_head(members, shape, lambda k: start[k:k + 1]),
            _by_head(members, shape, lambda k: end[k:k + 1]),
            _by_head(members, (shape[0], 1), lambda k: whole[k:k + 1]))


def _times(x, dt):
    """``x dt``: the product in float32, cast back."""
    return (x.astype(jnp.float32) * dt).astype(x.dtype)


def _ssd_scan_fwd_kernel(ct_ref, bt_ref, xt_ref, dt_ref, ccol_ref, crow_ref,
                         yt_ref, entering_ref, st_scr, state_scr, *, heads,
                         p):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    step = pl.program_id(2)
    ct, bt = ct_ref[0], bt_ref[0]                                 # (n, L)
    dtype = ct.dtype

    @pl.when(step == 0)
    def _first_heads_of_the_chunk():
        st_scr[...] = _dot(bt, ct, _AT_B)                         # (C B^T)^T

    @pl.when(pl.program_id(1) == 0)
    def _first_chunk_of_the_sequence():
        state_scr[step] = jnp.zeros(state_scr.shape[1:], f32)

    entering_ref[0] = state_scr[step]
    cum = crow_ref[0]                                             # (k, L)
    decays = _chunk_decays(cum)
    for first, rows, members in _slabs(heads, p):
        slab = slice(first, first + rows)
        x = xt_ref[0, slab, :]                                    # (rows, L)
        dt, from_start, to_end, kept = _slab_rows(members, x.shape, dt_ref,
                                                  decays)
        xt = _times(x, dt)
        state = state_scr[step, slab, :]                          # (rows, n)
        # the state the chunk was handed, read by every step of the chunk
        yt = _dot(state.astype(dtype), ct) * from_start
        within = [_dot(xt[lo:hi], (st_scr[...] * _decay_t(ccol_ref, cum, k))
                       .astype(dtype)) for k, lo, hi in members]
        yt_ref[0, slab, :] = (yt + jnp.concatenate(within, axis=0)) \
            .astype(yt_ref.dtype)
        # what the chunk adds to the state by its end
        added = _dot((xt.astype(f32) * to_end).astype(dtype), bt, _A_BT)
        state_scr[step, slab, :] = kept * state + added


def _ssd_scan_bwd_kernel(ct_ref, bt_ref, xt_ref, dt_ref, ccol_ref, crow_ref,
                         entering_ref, dyt_ref, dxt_ref, ddt_ref, dcum_ref,
                         dct_ref, dbt_ref, st_scr, dst_scr, dct_scr, dbt_scr,
                         dstate_scr, *, heads, p, steps):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    step = pl.program_id(2)
    ct, bt = ct_ref[0], bt_ref[0]                                 # (n, L)
    dtype = ct.dtype

    @pl.when(step == 0)
    def _first_heads_of_the_chunk():
        st_scr[...] = _dot(bt, ct, _AT_B)
        dst_scr[...] = jnp.zeros_like(dst_scr)
        dct_scr[...] = jnp.zeros_like(dct_scr)
        dbt_scr[...] = jnp.zeros_like(dbt_scr)

    @pl.when(pl.program_id(1) == 0)
    def _last_chunk_of_the_sequence():
        dstate_scr[step] = jnp.zeros(dstate_scr.shape[1:], f32)

    cum = crow_ref[0]                                             # (k, L)
    decays = _chunk_decays(cum)
    head = lax.broadcasted_iota(jnp.int32, cum.shape, 0)
    is_last = lax.broadcasted_iota(jnp.int32, (1, cum.shape[1]), 1) \
        == cum.shape[1] - 1
    dcum, ddt = jnp.zeros(cum.shape, f32), jnp.zeros(cum.shape, f32)
    dscores_t = None
    for first, rows, members in _slabs(heads, p):
        slab = slice(first, first + rows)
        x = xt_ref[0, slab, :]                                    # (rows, L)
        dt, from_start, to_end, kept = _slab_rows(members, x.shape, dt_ref,
                                                  decays)
        xt = _times(x, dt)
        dyt = dyt_ref[0, slab, :].astype(f32)
        x32 = xt.astype(f32)
        state = entering_ref[0, slab, :]                          # (rows, n)
        dleaving = dstate_scr[step, slab, :]
        # y_i += exp(cum_i) C_i . state
        entering = state.astype(dtype)
        dy_start = (dyt * from_start).astype(dtype)
        dct_scr[...] += _dot(entering, dy_start, _AT_B)
        dentering = _dot(dy_start, ct, _A_BT)
        yt = _dot(entering, ct) * from_start
        # leaving = kept * state + (x * to_end)^T B
        dleaving_c = dleaving.astype(dtype)
        dx_end = _dot(dleaving_c, bt)
        dbt_scr[...] += _dot(dleaving_c, (x32 * to_end).astype(dtype), _AT_B)
        dto_end = dx_end * x32 * to_end      # by its log, cum_last - cum_i
        dkept = dleaving * state * kept      # by its log, cum_last
        dstate_scr[step, slab, :] = kept * dleaving + dentering
        y_within, dx_within = [], []
        for k, lo, hi in members:
            x_k, dy_k = xt[lo:hi], dyt[lo:hi].astype(dtype)
            decay_t = _decay_t(ccol_ref, cum, k)
            mixed_t = (st_scr[...] * decay_t).astype(dtype)
            y_within.append(_dot(x_k, mixed_t))
            dx_within.append(_dot(dy_k, mixed_t, _A_BT))
            part = _dot(x_k, dy_k, _AT_B) * decay_t
            dscores_t = part if dscores_t is None else dscores_t + part
        dx_within = jnp.concatenate(dx_within, axis=0)
        # xt = x dt
        dxt = dx_end * to_end + dx_within
        dxt_ref[0, slab, :] = (dxt * dt).astype(dxt_ref.dtype)
        by_dt = dxt * x.astype(f32)
        # cum_i: as column i of the transposed decays (dy_i . y_i), as row i
        # of the chunk's own (x_i . dx_i) and in to_end; cum_last in to_end
        # and in kept
        by_step = dyt * (yt + jnp.concatenate(y_within, axis=0)) \
            - x32 * dx_within - dto_end
        for k, lo, hi in members:
            last = jnp.sum(dto_end[lo:hi], keepdims=True) \
                + jnp.sum(dkept[lo:hi], keepdims=True)
            steps_k = jnp.sum(by_step[lo:hi], axis=0, keepdims=True)
            dcum = jnp.where(head == k,
                             steps_k + jnp.where(is_last, last, 0.0), dcum)
            ddt = jnp.where(
                head == k, jnp.sum(by_dt[lo:hi], axis=0, keepdims=True), ddt)
    dcum_ref[0] = dcum
    ddt_ref[0] = ddt
    dst_scr[...] += dscores_t

    @pl.when(step == steps - 1)
    def _last_heads_of_the_chunk():
        ds_t = dst_scr[...].astype(dtype)
        dct_ref[0] = (dct_scr[...] + _dot(bt, ds_t)).astype(dct_ref.dtype)
        dbt_ref[0] = (dbt_scr[...] + _dot(ct, ds_t, _A_BT)) \
            .astype(dbt_ref.dtype)


def _operands(C, B, x, dt, cum):
    """The kernels' views of the scan's arrays, time the last axis of each
    (the layout XLA gives this program's activations on the TPU, so the
    transposes cost nothing there): ``C^T``, ``B^T`` (b, n, t), ``x^T``
    (b, h p, t), ``dt^T`` and ``cum^T`` (b, h, t), and ``cum`` once more a
    column a head, by chunk and step of :func:`heads_per_step` heads."""
    b, c, size, h, p = x.shape
    n = B.shape[-1]
    k = heads_per_step(h)
    time_last = lambda v, width: v.reshape(b, c * size, width) \
        .transpose(0, 2, 1)
    return (time_last(C, n), time_last(B, n), time_last(x, h * p),
            time_last(dt, h),
            cum.reshape(b * c, size, h // k, k).transpose(0, 2, 1, 3),
            time_last(cum, h))


def _specs(chunks, size, n, k, p, chunk_of):
    """Block specs over the grid (sequence, chunk, step of heads);
    ``chunk_of(j)`` is the chunk the grid's ``j``-th visits."""
    from jax.experimental import pallas as pl

    state_wide = pl.BlockSpec((1, n, size),
                              lambda i, j, s: (i, 0, chunk_of(j)))
    head_rows = pl.BlockSpec((1, k * p, size),
                             lambda i, j, s: (i, s, chunk_of(j)))
    cum_col = pl.BlockSpec(
        (1, 1, size, k), lambda i, j, s: (i * chunks + chunk_of(j), s, 0, 0))
    cum_row = pl.BlockSpec((1, k, size), lambda i, j, s: (i, s, chunk_of(j)))
    states = pl.BlockSpec(
        (1, k * p, n), lambda i, j, s: (i * chunks + chunk_of(j), s, 0))
    return state_wide, head_rows, cum_col, cum_row, states


# jax traces and lowers every ``pallas_call`` call site on its own (0.7 s a
# site for these kernels on a v5e's host: 27 sites in a ten-layer step); as
# ``jit``s the sites of one shape share one trace and one lowered function
@jax.jit
def _forward(C, B, x, dt, cum):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, c, size, h, p = x.shape
    n = B.shape[-1]
    k = heads_per_step(h)
    state_wide, head_rows, cum_col, cum_row, states = _specs(
        c, size, n, k, p, lambda j: j)
    yt, entering = pl.pallas_call(
        functools.partial(_ssd_scan_fwd_kernel, heads=k, p=p),
        out_shape=(_sds((b, h * p, c * size), x.dtype, x),
                   _sds((b * c, h * p, n), jnp.float32, x)),
        grid=(b, c, h // k),
        in_specs=[state_wide, state_wide, head_rows, cum_row, cum_col,
                  cum_row],
        out_specs=(head_rows, states),
        scratch_shapes=[pltpu.VMEM((size, size), jnp.float32),
                        pltpu.VMEM((h // k, k * p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        name="_ssd_scan_fwd_kernel",
        interpret=resolve_interpret(),
    )(*_operands(C, B, x, dt, cum))
    return yt.transpose(0, 2, 1).reshape(b, c, size, h, p), entering


@jax.jit
def _backward(C, B, x, dt, cum, entering, dy):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, c, size, h, p = x.shape
    n = B.shape[-1]
    k = heads_per_step(h)
    # from the last chunk to the first: the state's gradient runs backward
    state_wide, head_rows, cum_col, cum_row, states = _specs(
        c, size, n, k, p, lambda j: c - 1 - j)
    f32 = jnp.float32
    dxt, ddt, dcum, dct, dbt = pl.pallas_call(
        functools.partial(_ssd_scan_bwd_kernel, heads=k, p=p, steps=h // k),
        out_shape=(_sds((b, h * p, c * size), x.dtype, x),
                   _sds((b, h, c * size), f32, x),
                   _sds((b, h, c * size), f32, x),
                   _sds((b, n, c * size), C.dtype, x),
                   _sds((b, n, c * size), B.dtype, x)),
        grid=(b, c, h // k),
        in_specs=[state_wide, state_wide, head_rows, cum_row, cum_col,
                  cum_row, states, head_rows],
        out_specs=(head_rows, cum_row, cum_row, state_wide, state_wide),
        scratch_shapes=[pltpu.VMEM((size, size), f32),
                        pltpu.VMEM((size, size), f32),
                        pltpu.VMEM((n, size), f32),
                        pltpu.VMEM((n, size), f32),
                        pltpu.VMEM((h // k, k * p, n), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        name="_ssd_scan_bwd_kernel",
        interpret=resolve_interpret(),
    )(*_operands(C, B, x, dt, cum), entering,
      dy.astype(x.dtype).reshape(b, c * size, h * p).transpose(0, 2, 1))
    back = lambda v, like: v.transpose(0, 2, 1).reshape(like.shape)
    return (back(dct, C), back(dbt, B), back(dxt, x), back(ddt, dt),
            back(dcum, cum))


@jax.custom_vjp
def ssd_scan(C, B, x, dt, cum):
    """The chunked scan from a zero state.  C, B (b, c, L, n) and x
    (b, c, L, h, p) in the compute dtype; dt, positive, and cum, the
    log-decays summed from each chunk's start, (b, c, L, h) float32.
    Returns y (b, c, L, h, p) in the compute dtype.  The shapes are ones
    :func:`tiles` takes."""
    return _forward(C, B, x, dt, cum)[0]


def _ssd_scan_fwd(C, B, x, dt, cum):
    y, entering = _forward(C, B, x, dt, cum)
    return y, (C, B, x, dt, cum, entering)


def _ssd_scan_bwd(kept, dy):
    return _backward(*kept, dy)


ssd_scan.defvjp(_ssd_scan_fwd, _ssd_scan_bwd)


# declared costs (analysis/cost.py KERNEL_COSTS; docs/fusion.md): the
# products the kernels run, one pass over operands and results (``B^T`` and
# ``C^T`` stay in VMEM across a chunk's steps)
def _scan_sizes(eqn):
    """(tokens, chunk, state, heads x head width) of a scan's call."""
    ct, _, xt, _, ccol = (a.aval for a in eqn.invars[:5])
    b, n, t = (int(d) for d in ct.shape)
    return b * t, int(ccol.shape[2]), n, int(xt.shape[1])


def _scan_cost(eqn, within, around):
    """``within`` times the products inside a chunk (``C B^T`` a chunk, ``M
    xdt`` a head) and ``around`` products of (L, p) with (p, n) a head around
    the state; the decay matrices and three decays a step and head."""
    tokens, size, n, columns = _scan_sizes(eqn)
    return {
        "flops": 2 * tokens * (within * size * (n + columns)
                               + around * n * columns),
        "transcendentals": int(eqn.invars[3].aval.size) * (size + 3),
        "bytes_read": sum(_nbytes(v.aval) for v in eqn.invars),
        "bytes_written": _out_bytes(eqn),
    }


# forward: C B^T, M xdt; state C^T, (xdt to_end)^T B.  backward: those of the
# forward, dS B, dS^T C, M^T dy, dy xdt^T; five around the state
_declare_cost("_ssd_scan_fwd_kernel")(
    functools.partial(_scan_cost, within=1, around=2))
_declare_cost("_ssd_scan_bwd_kernel")(
    functools.partial(_scan_cost, within=3, around=5))
