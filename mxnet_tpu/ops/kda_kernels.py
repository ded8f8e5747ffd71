"""The chunked delta rule of ``transformer/kda.py`` as a Pallas kernel pair.

``kda.kda_chunked`` computes Kimi Delta Attention's recurrence a chunk of
``L`` tokens at a time (its docstring has the algebra): a unit lower
triangular system a chunk and head, exponents relative to the middle of
sub-blocks of :data:`SUB_BLOCK` tokens, the state carried from chunk to
chunk.  Spelled in ``jax.numpy`` that is some ninety thousand small device
operations a step at ``Ling-3.0-flash``'s sizes, thirty passes over 268 MB
arrays a layer, and a forward pass that runs three times a step under two
nested checkpoints (``PERF.md`` section 6, PR 38 and 39).  :func:`kda_scan`
is the same map behind a ``jax.custom_vjp`` whose forward and backward are one
kernel each over the grid (sequence, step of :data:`STEP_HEADS` heads,
chunk): the chunks of a sequence in order (last to first in the backward
pass), the state (its gradient) carried in VMEM scratch, every ``L x L``
array, the decayed copies of ``q`` and ``k``, the inverse, ``W`` and ``U`` in
VMEM only.  The residuals are the five inputs and the state each chunk was
handed (float32, ``E x E`` a chunk and head); the backward kernel rebuilds
everything else of a chunk from them.

**Operands are read as the mixer leaves them**: ``q``, ``k``, ``v``, ``g``
``(b, t, H E)``, a head's 128 columns one lane tile and a chunk's tokens the
sublanes; ``beta`` a column a head.  The state is held transposed, ``S^T``
(value column, key column), so that what differs by key channel (the decay
a chunk keeps, ``e^{G_L}``) scales lanes.

A grid step, for each of its heads (``K``, ``Q``, ``V`` the chunk's rows,
``G`` the running sum of ``g``, ``r_a`` its value in the middle of sub-block
``a``)::

    G, G - r_a            two sums of g as one product with a 0/+-1 matrix
    A = tril(K e^{G-r} (K e^{r-G})^T, -1) beta,  P = tril(Q e^{G-r} (..)^T)
                          a sub-block's 16 rows of both as one product
    T = (I + A)^-1        forward substitution in the 16-row diagonal blocks
                          (all blocks of all the step's heads at once, one
                          vector operation a row), then from halves:
                          T - T (A below the diagonal of a pair) T
    [W U] = T [K e^G beta, V beta]
    u = U - W S,  o = (Q e^G) S + P u,  S' = e^{G_L} S + (K e^{G_L-G})^T u

The backward kernel differentiates exactly that, rounding to the compute
dtype read as the identity: with ``dT = dW (K e^G beta)^T + dU (V beta)^T``,
``dA = -tril(T^T dT T^T, -1)``; the gradients of ``K`` and ``Q`` come from
the rows' and the columns' factors sub-block by sub-block as the forward
products went; ``dG`` is each factor's gradient times the factor, a
reference sum ``r_a`` taking what its columns' factors give less what its
rows' give (nothing on paper; with rounded operands the residue that keeps
a chunk's ``dG`` adding up as the shifts of ``G`` that change nothing
demand), and ``dg`` its sum from the token to the chunk's end.

What is float32 whatever the compute dtype: the gate, its sums and every
exponential, ``beta``, ``A``, the substitution and the products of the
halves (at the highest precision), the carried state and its gradient,
the accumulation of every product, ``dg`` and ``dbeta``.  The sums of ``g``
are products with matrices of 0 and +-1 over ``g`` split into three
bfloat16 terms: exact.  The operands of the other products are of the
compute dtype, as ``kda_chunked``'s are.

:func:`tiles` is the rule that says which shapes the kernels take;
compile-or-interpret is ``pallas_kernels.resolve_interpret``'s.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..analysis.cost import declare_kernel_cost as _declare_cost
from .pallas_kernels import (LANES, _A_B, _A_BT, _dot, _nbytes, _out_bytes,
                             _sds, resolve_interpret)

__all__ = ["kda_scan", "tiles", "heads_per_step", "SUB_BLOCK", "CHUNK"]

# tokens whose exponents share a reference (``kda.SUB_BLOCK``)
SUB_BLOCK = 16
# the chunk the kernels are written for: four sub-blocks, two halvings
CHUNK = 64
# heads a grid step holds: their substitutions share vector operations and
# their products fill the pipeline while a head's own wait on each other
STEP_HEADS = 8

_AT_B = (((0,), (0,)), ((), ()))            # a^T b
_BATCH = (((2,), (1,)), ((0,), (0,)))       # a batch of a b


def tiles(chunk, heads, head_dim, dtype):
    """Whether the kernels take a scan of these shapes: chunks of
    :data:`CHUNK` tokens, heads of 128 key and value columns (one lane
    tile), in steps of :data:`STEP_HEADS` or fewer than that in all,
    bfloat16 or float32 operands.  A pure function of shapes and dtype: one
    shape traces one spelling."""
    if chunk != CHUNK or head_dim != LANES:
        return False
    if heads % STEP_HEADS and heads > STEP_HEADS:
        return False
    return jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                jnp.dtype(jnp.float32))


def heads_per_step(heads):
    """Heads a grid step holds: :data:`STEP_HEADS`, or all of fewer."""
    return heads if heads % STEP_HEADS else STEP_HEADS


def _times(x, y, dims=_A_B):
    """A float32 product at full precision."""
    return lax.dot_general(x, y, dims, precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _thirds(x):
    """Float32 ``x`` as three bfloat16 terms that sum to it."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    high = x.astype(bf16)
    rest = x - high.astype(f32)
    mid = rest.astype(bf16)
    return high, mid, (rest - mid.astype(f32)).astype(bf16)


def _selected(matrix, x):
    """``matrix x`` for a ``matrix`` of 0 and +-1 in bfloat16 and ``x``
    float32: every product exact, accumulated in float32."""
    return sum(_dot(matrix, part) for part in _thirds(x))


def _folded(x, matrix):
    """``x matrix``, as :func:`_selected`."""
    return sum(_dot(part, matrix) for part in _thirds(x))


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


class _Constants:
    """The masks and 0/+-1 matrices of a chunk of ``size`` tokens in
    sub-blocks of ``sub`` (a power of two), built from iotas."""

    def __init__(self, size, sub, width):
        bf16 = jnp.bfloat16
        self.size, self.sub = size, sub
        row, col = _iota((size, size), 0), _iota((size, size), 1)
        self.below, self.at_or_below = row > col, row >= col
        first = row - (row & (sub - 1))      # where a row's sub-block starts
        mid = first + (sub // 2 - 1)
        # G_i = sum_{j <= i} g_j; G_i - r_a(i) = +-sum between i and the
        # middle of its sub-block; sum_{j >= i} for the way back
        self.sums = jnp.concatenate([
            (col <= row).astype(bf16),
            (((col > mid) & (col <= row)).astype(jnp.float32)
             - ((col > row) & (col <= mid)).astype(jnp.float32)
             ).astype(bf16)], axis=0)
        self.to_end = (col >= row).astype(bf16)
        self.own = (col >= first) & (col < first + sub)
        # column 16 a + j of a row of sub-block a -> column j
        self.fold = ((_iota((size, sub), 0) & (sub - 1))
                     == _iota((size, sub), 1)).astype(bf16)
        self.eye = (row == col).astype(jnp.float32)
        # the block below the diagonal of each pair of blocks of w rows
        self.pairs = []
        w = sub
        while w < size:
            block = row - (row & (w - 1))
            self.pairs.append(((block & w) != 0) & (col < block)
                              & (col >= block - w))
            w *= 2
        self.token = _iota((size, width), 0)


def _system(c, q, k, g, beta):
    """A chunk's factors and its system, of one head.  q, k (L, E) in the
    compute dtype, g (L, E) and beta (L, 1) float32."""
    f32, dtype = jnp.float32, q.dtype
    size, sub = c.size, c.sub
    n = size // sub
    both = _selected(c.sums, g)
    total, relative = both[:size], both[size:]   # G, G - r_a
    down = jnp.exp(relative)
    # e^{r_a - G_j} for the columns j a row of sub-block a sees: those of
    # earlier sub-blocks (at most 1) and of its own (within e^+-40)
    ups = []
    for a in range(n):
        mid = a * sub + sub // 2 - 1
        ups.append(jnp.exp(jnp.where(c.token < (a + 1) * sub,
                                     total[mid:mid + 1] - total, -jnp.inf)))
    k32, q32 = k.astype(f32), q.astype(f32)
    k_rows, q_rows = (k32 * down).astype(dtype), (q32 * down).astype(dtype)
    k_cols = [(k32 * up).astype(dtype) for up in ups]
    products = [_dot(jnp.concatenate([k_rows[a * sub:(a + 1) * sub],
                                      q_rows[a * sub:(a + 1) * sub]], axis=0),
                     k_cols[a], _A_BT) for a in range(n)]     # (2 sub, L)
    raw = jnp.where(c.below, jnp.concatenate(
        [p[:sub] for p in products], axis=0), 0.0)
    scores = jnp.where(c.at_or_below, jnp.concatenate(
        [p[sub:] for p in products], axis=0), 0.0)
    return dict(total=total, down=down, ups=ups, k32=k32, q32=q32,
                k_rows=k_rows, q_rows=q_rows, k_cols=k_cols, raw=raw,
                scores=scores, system=raw * beta)


def _inverse(c, systems):
    """``(I + a)^-1`` of each strictly lower triangular ``a`` (L, L) of
    ``systems``, float32: rows of the 16-row diagonal blocks by forward
    substitution, every block of every system in the same vector
    operations (row ``j`` of a block, once final, is taken off the rows
    below it), then from halves, ``[[T1, 0], [-T2 a21 T1, T2]]``.  Never
    the product ``(I - a)(I + a^2)...`` (``kda._unit_lower_inverse``)."""
    size, sub = c.size, c.sub
    n, h = size // sub, len(systems)
    a = jnp.stack(systems)                                     # (h, L, L)
    own = _folded(jnp.where(c.own, a, 0.0).reshape(h * size, size),
                  c.fold).reshape(h * n, sub, sub)
    t = jnp.concatenate([c.eye.reshape(n, sub, size)] * h, axis=0)
    for j in range(sub - 1):
        t = t - own[:, :, j:j + 1] * t[:, j:j + 1, :]
    t = t.reshape(h, size, size)
    for below in c.pairs:
        t = t - _times(t, _times(jnp.where(below, a, 0.0), t, _BATCH),
                       _BATCH)
    return [t[i] for i in range(h)]


def _chunk(c, s, t, v, beta, state):
    """What a chunk's forward and backward passes share once its system is
    inverted: the operands of the products around the state."""
    f32, dtype = jnp.float32, v.dtype
    size = c.size
    total, k32 = s["total"], s["k32"]
    decay = jnp.exp(total)                                     # e^{G_i}
    last = total[size - 1:]                                    # G_L
    to_end = jnp.exp(last - total)
    k_decayed = k32 * decay
    inverse = t.astype(dtype)
    sides = jnp.concatenate([(k_decayed * beta).astype(dtype),
                             (v.astype(f32) * beta).astype(dtype)], axis=1)
    both = _dot(inverse, sides)                                # [W U]
    width = k32.shape[1]
    w = both[:, :width].astype(dtype)
    handed = state.astype(dtype)
    return dict(decay=decay, to_end=to_end, kept=jnp.exp(last),
                k_decayed=k_decayed, inverse=inverse, sides=sides, w=w,
                u_own=both[:, width:], handed=handed,
                q_decayed=(s["q32"] * decay).astype(dtype),
                k_end32=k32 * to_end)


def _columns(i, width):
    """A step's head ``i`` in the ``heads * width`` columns of a block."""
    return slice(i * width, (i + 1) * width)


def _systems(q_ref, k_ref, g_ref, beta_ref, heads, sub):
    """What both kernels start a grid step with: the constants, every
    head's ``beta`` column, factors and system, and the systems' inverses."""
    size, width = q_ref.shape[1], q_ref.shape[2] // heads
    c = _Constants(size, sub, width)
    betas = [beta_ref[0, 0, :, i:i + 1] for i in range(heads)]
    systems = [_system(c, q_ref[0, :, _columns(i, width)],
                       k_ref[0, :, _columns(i, width)],
                       g_ref[0, :, _columns(i, width)], betas[i])
               for i in range(heads)]
    inverses = _inverse(c, [s["system"] for s in systems])
    return c, betas, systems, inverses


def _kda_scan_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest,
                         heads, sub, emit):
    from jax.experimental import pallas as pl

    entering_ref = rest[0] if emit else None
    state_scr = rest[-1]
    f32 = jnp.float32
    size, width = q_ref.shape[1], q_ref.shape[2] // heads
    dtype = q_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk_of_the_sequence():
        state_scr[...] = jnp.zeros(state_scr.shape, f32)

    c, betas, systems, inverses = _systems(q_ref, k_ref, g_ref, beta_ref,
                                           heads, sub)
    for i, (s, t) in enumerate(zip(systems, inverses)):
        columns = _columns(i, width)
        state = state_scr[i]                                   # S^T (p, d)
        if emit:
            entering_ref[0, i] = state
        x = _chunk(c, s, t, v_ref[0, :, columns], betas[i], state)
        # [W; Q e^G] S in one product
        read = _dot(jnp.concatenate([x["w"], x["q_decayed"]], axis=0),
                    x["handed"], _A_BT)
        u = (x["u_own"] - read[:size]).astype(dtype)
        o = read[size:] + _dot(s["scores"].astype(dtype), u)
        o_ref[0, :, columns] = o.astype(o_ref.dtype)
        state_scr[i] = x["kept"] * state + _dot(
            u, x["k_end32"].astype(dtype), _AT_B)


def _kda_scan_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, entering_ref,
                         do_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                         dstate_scr, *, heads, sub):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    size, width = q_ref.shape[1], q_ref.shape[2] // heads
    dtype = q_ref.dtype
    n = size // sub

    @pl.when(pl.program_id(2) == 0)
    def _last_chunk_of_the_sequence():
        dstate_scr[...] = jnp.zeros(dstate_scr.shape, f32)

    c, betas, systems, inverses = _systems(q_ref, k_ref, g_ref, beta_ref,
                                           heads, sub)
    dbetas = []
    for i, (s, t) in enumerate(zip(systems, inverses)):
        columns = _columns(i, width)
        beta, k32, q32 = betas[i], s["k32"], s["q32"]
        v = v_ref[0, :, columns]
        state = entering_ref[0, i]                             # S^T (p, d)
        x = _chunk(c, s, t, v, beta, state)
        handed, w, k_end32 = x["handed"], x["w"], x["k_end32"]
        u = (x["u_own"] - _dot(w, handed, _A_BT)).astype(dtype)
        k_end = k_end32.astype(dtype)
        do = do_ref[0, :, columns]
        dleaving = dstate_scr[i]
        dleaving_c = dleaving.astype(dtype)
        # o = (Q e^G) S + P u;  S' = e^{G_L} S + (K e^{G_L - G})^T u
        dq_decayed = _dot(do, handed)
        dstate = _dot(do, x["q_decayed"], _AT_B) + dleaving * x["kept"]
        dscores = jnp.where(c.at_or_below, _dot(do, u, _A_BT), 0.0)
        du = (_dot(s["scores"].astype(dtype), do, _AT_B)
              + _dot(k_end, dleaving_c, _A_BT)).astype(dtype)
        dk_end = _dot(u, dleaving_c) * x["to_end"]
        dlast = (jnp.sum(dleaving * state, axis=0, keepdims=True) * x["kept"]
                 + jnp.sum(dk_end * k32, axis=0, keepdims=True))
        # u = U - W S;  [W U] = T [K e^G beta, V beta]
        dw = (-_dot(du, handed)).astype(dtype)
        dstate_scr[i] = dstate - _dot(du, w, _AT_B)
        dboth = jnp.concatenate([dw, du], axis=1)
        dinverse = _dot(dboth, x["sides"], _A_BT)
        dsides = _dot(x["inverse"], dboth, _AT_B)
        dk_side, dv_side = dsides[:, :width], dsides[:, width:]
        # T = (I + A)^-1;  A = tril(.., -1) beta
        dsystem = jnp.where(c.below, -_times(
            _times(t, dinverse, _AT_B), t, _A_BT), 0.0)
        dbetas.append(jnp.sum(dsystem * s["raw"], axis=1, keepdims=True)
                      + jnp.sum(dk_side * x["k_decayed"]
                                + dv_side * v.astype(f32),
                                axis=1, keepdims=True))
        draw = dsystem * beta
        # the rows' and the columns' factors, a sub-block's rows at a time
        drows, by_columns, seen = [], jnp.zeros((size, width), f32), []
        for a in range(n):
            rows = slice(a * sub, (a + 1) * sub)
            both = jnp.concatenate([draw[rows], dscores[rows]],
                                   axis=0).astype(dtype)       # (2 sub, L)
            drows.append(_dot(both, s["k_cols"][a]))
            columns_a = s["ups"][a] * _dot(
                both, jnp.concatenate([s["k_rows"][rows], s["q_rows"][rows]],
                                      axis=0), _AT_B)
            by_columns = by_columns + columns_a
            seen.append(jnp.sum(columns_a * k32, axis=0, keepdims=True))
        by_k_rows = s["down"] * jnp.concatenate(
            [d[:sub] for d in drows], axis=0)
        by_q_rows = s["down"] * jnp.concatenate(
            [d[sub:] for d in drows], axis=0)
        dq = by_q_rows + dq_decayed * x["decay"]
        by_side = dk_side * x["decay"] * beta
        dq_ref[0, :, columns] = dq.astype(dq_ref.dtype)
        dk_ref[0, :, columns] = (by_k_rows + by_columns + by_side
                                 + dk_end).astype(dk_ref.dtype)
        dv_ref[0, :, columns] = (dv_side * beta).astype(dv_ref.dtype)
        # every factor's gradient times the factor, by its exponent.  The
        # reference sum r_a takes what its columns' factors give less what
        # its rows' give: nothing on paper, and in rounded operands what
        # keeps the gradients of a chunk's sums adding up to what the
        # shifts of ``G`` that change nothing demand (left out, ``dg`` read
        # 1.9 times the chunked form's error against the recurrence)
        by_rows = k32 * by_k_rows + q32 * by_q_rows
        dtotal = k32 * (by_side - by_columns - dk_end) + by_rows \
            + q32 * dq_decayed * x["decay"]
        for a in range(n):
            mid = a * sub + sub // 2 - 1
            dtotal = dtotal + jnp.where(
                c.token == mid, seen[a] - jnp.sum(
                    by_rows[a * sub:(a + 1) * sub], axis=0, keepdims=True),
                0.0)
        dg_ref[0, :, columns] = _selected(c.to_end, dtotal) + dlast
    head = _iota((size, heads), 1)
    dbeta_ref[0, 0] = sum(jnp.where(head == i, d, 0.0)
                          for i, d in enumerate(dbetas))


def _specs(chunks, size, heads, width, chunk_of):
    """Block specs over the grid (sequence, step of heads, chunk);
    ``chunk_of(j)`` is the chunk the grid's ``j``-th visits."""
    from jax.experimental import pallas as pl

    tokens = pl.BlockSpec((1, size, heads * width),
                          lambda i, s, j: (i, chunk_of(j), s))
    betas = pl.BlockSpec((1, 1, size, heads),
                         lambda i, s, j: (i, s, chunk_of(j), 0))
    states = pl.BlockSpec((1, heads, width, width),
                          lambda i, s, j: (i * chunks + chunk_of(j), s, 0, 0))
    return tokens, betas, states


def _by_step(beta, heads):
    """beta (b, t, h) a step's heads together: (b, h / heads, t, heads)."""
    b, t, h = beta.shape
    return beta.reshape(b, t, h // heads, heads).transpose(0, 2, 1, 3)


def _params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


# as ``jit``s the call sites of one shape share one trace and one lowered
# function (``ssd_kernels._forward``)
@functools.partial(jax.jit, static_argnames=("emit",))
def _forward(q, k, v, g, beta, *, emit):
    """q, k, v, g (b, t, h e), beta (b, t, h); ``t`` whole chunks.  Returns
    (o, the state each chunk was handed or None)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, h = beta.shape
    width = q.shape[2] // h
    chunks = t // CHUNK
    step = heads_per_step(h)
    tokens, betas, states = _specs(chunks, CHUNK, step, width, lambda j: j)
    out_shape = [_sds(q.shape, q.dtype, q)]
    out_specs = [tokens]
    if emit:
        out_shape.append(_sds((b * chunks, h, width, width), jnp.float32, q))
        out_specs.append(states)
    out = pl.pallas_call(
        functools.partial(_kda_scan_fwd_kernel, heads=step, sub=SUB_BLOCK,
                          emit=emit),
        out_shape=out_shape,
        grid=(b, h // step, chunks),
        in_specs=[tokens] * 4 + [betas],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((step, width, width), jnp.float32)],
        compiler_params=_params(),
        name="_kda_scan_fwd_kernel",
        interpret=resolve_interpret(),
    )(q, k, v, g, _by_step(beta, step))
    return out[0], (out[1] if emit else None)


@jax.jit
def _backward(q, k, v, g, beta, entering, do):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, h = beta.shape
    width = q.shape[2] // h
    chunks = t // CHUNK
    step = heads_per_step(h)
    # from the last chunk to the first: the state's gradient runs backward
    tokens, betas, states = _specs(chunks, CHUNK, step, width,
                                   lambda j: chunks - 1 - j)
    f32 = jnp.float32
    dq, dk, dv, dg, dbeta = pl.pallas_call(
        functools.partial(_kda_scan_bwd_kernel, heads=step, sub=SUB_BLOCK),
        out_shape=(_sds(q.shape, q.dtype, q), _sds(k.shape, k.dtype, q),
                   _sds(v.shape, v.dtype, q), _sds(g.shape, f32, q),
                   _sds((b, h // step, t, step), f32, q)),
        grid=(b, h // step, chunks),
        in_specs=[tokens] * 4 + [betas, states, tokens],
        out_specs=(tokens, tokens, tokens, tokens, betas),
        scratch_shapes=[pltpu.VMEM((step, width, width), f32)],
        compiler_params=_params(),
        name="_kda_scan_bwd_kernel",
        interpret=resolve_interpret(),
    )(q, k, v, g, _by_step(beta, step), entering, do.astype(q.dtype))
    return dq, dk, dv, dg, dbeta.transpose(0, 2, 1, 3).reshape(b, t, h)


@jax.custom_vjp
def _scan(q, k, v, g, beta):
    return _forward(q, k, v, g, beta, emit=False)[0]


def _scan_fwd(q, k, v, g, beta):
    o, entering = _forward(q, k, v, g, beta, emit=True)
    return o, (q, k, v, g, beta, entering)


def _scan_bwd(kept, do):
    return _backward(*kept, do)


_scan.defvjp(_scan_fwd, _scan_bwd)


def kda_scan(q, k, v, g, beta):
    """The map of ``kda.kda_chunked`` at chunks of :data:`CHUNK` from a zero
    state.  q, k, v (b, t, h, e) in the compute dtype, ``q`` already scaled;
    g (b, t, h, e), the log of the decay, in (-5, 0]; beta (b, t, h).
    Returns o (b, t, h, e) in the compute dtype.  The shapes are ones
    :func:`tiles` takes.  A sequence that is no multiple of the chunk is
    padded at its end with tokens of ``g = 0``, ``beta = 0`` and ``k = 0``,
    which no earlier token sees."""
    b, t, h, e = q.shape
    padded = -(-t // CHUNK) * CHUNK
    f32 = jnp.float32

    def flat(a):
        a = a.reshape(b, t, -1)
        return jnp.pad(a, [(0, 0), (0, padded - t), (0, 0)]) \
            if padded != t else a

    o = _scan(flat(q), flat(k), flat(v), flat(g.astype(f32)),
              flat(beta.astype(f32)))
    return o[:, :t].reshape(b, t, h, e)


# declared costs (analysis/cost.py KERNEL_COSTS; docs/fusion.md): the
# products the kernels run, one pass over operands and results
def _scan_cost(eqn, wide, square, exact, full, factors):
    """Of a call over ``tokens x heads`` rows of ``E`` columns in chunks of
    ``L`` = :data:`CHUNK`: ``wide`` products of (L, L) with (L, E) and
    ``square`` of (L, E) with (E, E) in the compute dtype, ``exact`` sums of
    three bfloat16 terms against an (L, L) matrix, ``full`` float32 products
    of (L, L) squares at six passes each, the substitution's rows, and
    ``factors`` exponentials a token and column."""
    q, beta = eqn.invars[0].aval, eqn.invars[4].aval
    rows = int(beta.size)                                      # tokens x heads
    size = CHUNK
    e = int(q.shape[2]) // (int(beta.shape[1]) * int(beta.shape[3]))
    return {
        "flops": 2 * rows * (wide * size * e + square * e * e
                             + 3 * exact * size * e + 6 * full * size * size
                             + SUB_BLOCK * size),
        "transcendentals": rows * e * factors,
        "bytes_read": sum(_nbytes(v.aval) for v in eqn.invars),
        "bytes_written": _out_bytes(eqn),
    }


# forward: the rows against the columns (the scores and the system as one),
# [W U] twice as wide, P u; [W; Q e^G] S twice, the state's update; the two
# sums of g; four products of the halves; down, four ups, e^G, e^{G_L - G}.
# backward: those but P u, Q S and the state's update, and dP, P^T do, dT
# and T^T [dW dU] twice as wide, the rows' and the columns' gradients twice;
# do S, do^T Q, K_end dS', u dS', du S, du^T W; the sum to the chunk's end;
# T^T dT T^T
_declare_cost("_kda_scan_fwd_kernel")(functools.partial(
    _scan_cost, wide=4, square=3, exact=2, full=4, factors=8))
_declare_cost("_kda_scan_bwd_kernel")(functools.partial(
    _scan_cost, wide=13, square=7, exact=3, full=6, factors=8))
