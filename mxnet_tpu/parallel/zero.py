"""ZeRO-1 sharded optimizer runtime (arxiv 2004.13336) for
``DataParallelTrainer(zero=1)``.

PR 11 proved the ZeRO-1 weight update *statically* (the
``zero1_mlp_train_step`` budget model and DST006-DST010); this module is
the runtime half.  The step is spelled **per replica** once and used two
ways, so the executed program and the analyzed program can never drift:

- **runtime**: the same per-replica functions run under ``shard_map``
  over the trainer's mesh as two jitted programs — ``grad_fn`` (forward
  + backward + reduce-scatter of the flat gradient) and ``update_fn``
  (shard-local optimizer update + all-gather of the new params).  The
  optimizer state lives as ONE flat ``(padded,)`` array per state leaf,
  sharded ``P(axis)`` over the data axis: each device physically holds
  ``1/K`` of it — the ZeRO-1 memory saving is real, not modeled.  The
  two-program split mirrors ``_enqueue_dist``'s grad→exchange→update shape,
  which is what lets the performance doctor bill the reduce-scatter/
  all-gather program to the ``collective_or_ps`` phase.
- **analysis**: :func:`build_replica_step` composes the same two parts
  into one function traced with ``jax.make_jaxpr(axis_env=[(axis, K)])``
  — no devices — for the mxcost tape, the DST lint and the
  ``STATIC_BUDGETS.json`` runtime-parity checks
  (``analysis/budget_models.zero1_mlp_train_step``).

Flat layout: every trainable parameter raveled (f32) and concatenated in
``collect_params`` order, zero-padded to a multiple of K.  Rank ``r``
owns the contiguous ``[r*shard, (r+1)*shard)`` slice of that flat space
— ``psum_scatter`` lands exactly the owned gradient shard, the update is
shard-local, ``all_gather(tiled=True)`` reassembles the flat vector.
The padding tail provably stays zero across steps (gradients pad with
zeros, so every elementwise optimizer maps a zero (w, g, state) tail to
a zero tail), which is what makes resize-on-resume checkpointing exact:
a shard set saved at fleet size K truncates to the unpadded ``total``
and re-pads for any other size bitwise-losslessly
(``resilience/checkpoint.py`` sharded snapshots, docs/elastic.md).

``ZERO1_RUNTIME_ALL_GATHER`` is the runtime mutation seam (the
shard-fixture ``ZERO1_ALL_GATHER`` discipline): tests flip it from a
subprocess to prove that deleting the runtime all-gather fails the
``STATIC_BUDGETS.json`` gate with DST007 named.  Production code never
touches it.
"""
from __future__ import annotations

import numpy as _np

__all__ = ["ZERO1_RUNTIME_ALL_GATHER", "Zero1Plan", "build_parts",
           "build_replica_step", "build_runtime_fns", "reassemble_state",
           "reshard_full"]

# runtime mutation seam (see module docstring) — flipped only by tests
ZERO1_RUNTIME_ALL_GATHER = True


class Zero1Plan:
    """The flat parameter layout of one ZeRO-1 trainer over ``axis``.

    Pure shapes arithmetic (no jax): names/shapes/dtypes in parameter
    order, the flat ``total``, the K-padded length and the per-rank
    ``shard`` size.  Deterministic given (parameters, K) — both the
    runtime and the resize-on-resume restore path derive their slicing
    from it, so a fleet of a different size re-shards identically.
    """

    def __init__(self, names, shapes, dtypes, axis, k):
        self.names = list(names)
        self.shapes = [tuple(int(d) for d in s) for s in shapes]
        self.dtypes = [str(d) for d in dtypes]
        self.axis = str(axis)
        self.k = int(k)
        if self.k < 1:
            raise ValueError("zero=1 needs a data axis of size >= 1, "
                             "got %d" % self.k)
        self.sizes = [int(_np.prod(s)) if s else 1 for s in self.shapes]
        self.total = int(sum(self.sizes))
        self.padded = -(-self.total // self.k) * self.k
        self.shard = self.padded // self.k

    def describe(self):
        """JSON-able layout record embedded in sharded checkpoints so a
        restore at a different fleet size can re-derive the slicing."""
        return {"names": list(self.names), "shapes": [list(s) for s in
                                                      self.shapes],
                "dtypes": list(self.dtypes), "axis": self.axis,
                "k": self.k, "total": self.total, "padded": self.padded,
                "shard": self.shard}


def _flatten_pad(vals, plan, jnp, dtype=None):
    dtype = jnp.float32 if dtype is None else dtype
    parts = [v.ravel().astype(dtype) for v in vals]
    pad = plan.padded - plan.total
    if pad:
        parts.append(jnp.zeros((pad,), dtype))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def _unflatten(flat, plan, jnp):
    out, off = [], 0
    for shape, size, dt in zip(plan.shapes, plan.sizes, plan.dtypes):
        out.append(flat[off:off + size].reshape(shape)
                   .astype(_np.dtype(dt)))
        off += size
    return tuple(out)


def build_parts(fwd, opt, plan, state_treedef, compute_dtype=None,
                grad_accum=1):
    """``(grads_part, update_part)`` — the per-replica halves of the
    ZeRO-1 step.  Both are pure jax functions over LOCAL shards (the
    ``shard_map`` / ``axis_env`` view):

    - ``grads_part(train_vals, aux_vals, x, y, key) -> (g_shard, loss,
      muts)``: forward + backward on the local batch shard, flat
      gradient reduce-scattered over ``plan.axis`` (mean), loss and
      BatchNorm batch statistics pmean'd — the step's ONE gradient
      reduction point (DST001/DST006 subject).
    - ``update_part(train_vals, state_leaves, g_shard, lr, t) ->
      (new_vals, new_state_leaves)``: the rank's flat weight shard
      sliced out, the SAME ``Optimizer.update`` code as the eager path
      applied shard-locally, the new params all-gathered back whole
      (the DST007 pair).

    With ``compute_dtype=bfloat16`` (``mxnet_tpu.precision``,
    docs/precision.md) the halves grow the mixed-precision signature
    instead: params/activations are bf16, the f32 MASTER weights live
    only as the ``(shard,)`` slice each rank owns (they never
    materialize unsharded — the arxiv 2004.13336 layout), gradients are
    cast f32 BEFORE the reduce-scatter (the tightened DST004 subject),
    the loss-scale grow/backoff tick and the inf/nan select-skip ride
    the update, and the all-gather reassembles the params ALREADY cast
    bf16 — half the wire and param-HBM bytes:

    - ``grads_part(train_vals, aux_vals, x, y, key, scale) ->
      (g_shard_f32, loss, muts, grads_finite)``
    - ``update_part(train_vals, master_shard, state_leaves, g_shard,
      lr, t, scale, good_steps, skipped, grads_finite) ->
      (new_vals_bf16, new_master_shard, new_state_leaves, new_scale,
      new_good_steps, new_skipped)``
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from .functional import functional_optimizer_update
    from .step import local_grads

    axis, k, shard = plan.axis, plan.k, plan.shard

    if compute_dtype is not None and \
            jnp.dtype(compute_dtype) != jnp.float32:
        return _build_parts_reduced(fwd, opt, plan, state_treedef,
                                    jnp.dtype(compute_dtype), grad_accum)

    # under grad_accum (docs/distributed.md) the shard-local batch is
    # accumulated over its microbatches first, then ONE reduce-scatter of
    # the flat gradient: the collective count and wire bytes are
    # unchanged, which keeps DST006's one-reduction contract
    grads_of = local_grads(fwd, grad_accum=grad_accum)

    def grads_part(train_vals, aux_vals, x, y, key):
        loss_val, muts, grads = grads_of(train_vals, aux_vals, x, y, key)
        # reduce-scatter lands exactly this rank's owned gradient
        # shard; /k turns the psum semantics into the gradient mean
        # every replicated spelling uses
        with jax.named_scope("grad_reduce"):
            flat_g = _flatten_pad(grads, plan, jnp)
            g_sh = lax.psum_scatter(flat_g, axis, scatter_dimension=0,
                                    tiled=True) / k
            loss_val = lax.pmean(loss_val, axis)
            muts = tuple(lax.pmean(m, axis) for m in muts)
        return g_sh, loss_val, muts

    @jax.named_scope("optimizer_update")
    def update_part(train_vals, state_leaves, g_sh, lr, t):
        from ..ops import fused_optimizer as _fused

        flat_w = _flatten_pad(train_vals, plan, jnp)
        idx = lax.axis_index(axis)
        w_sh = lax.dynamic_slice(flat_w, (idx * shard,), (shard,))
        state = jax.tree_util.tree_unflatten(state_treedef,
                                             list(state_leaves))
        if _fused.fused_update_enabled() and _fused.supports(opt):
            # the rs → FUSED-update → ag spelling (docs/fusion.md): the
            # shard-local optimizer chain runs as one Pallas pass over
            # the owned 1/K slice; state stays physically sharded and
            # the kernel's numerics mirror Optimizer.update exactly
            new_w_sh, new_state = _fused.fused_optimizer_update(
                opt, 0, w_sh, g_sh, state, lr, t)
        else:
            new_w_sh, new_state = functional_optimizer_update(
                opt, 0, w_sh, g_sh, state, lr, t)
        if ZERO1_RUNTIME_ALL_GATHER:
            new_flat = lax.all_gather(new_w_sh, axis, tiled=True)
        else:
            # the classic broken spelling (tests only): the rank's own
            # shard tiled out as if it were the gathered whole — every
            # rank's params become mostly some other rank's bytes
            new_flat = jnp.concatenate([new_w_sh] * k) if k > 1 \
                else new_w_sh
        new_vals = _unflatten(new_flat, plan, jnp)
        return new_vals, tuple(jax.tree_util.tree_leaves(new_state))

    return grads_part, update_part


def _build_parts_reduced(fwd, opt, plan, state_treedef, compute_dtype,
                         grad_accum=1):
    """The mixed-precision halves (see :func:`build_parts` docstring):
    bf16 compute, f32 masters-in-the-shard, f32 gradient reduction,
    loss scaling with select-skip.  Split out so the f32 spelling's
    traced program stays byte-identical."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from .. import precision as _prec
    from .functional import functional_optimizer_update
    from .step import local_grads

    axis, k, shard = plan.axis, plan.k, plan.shard

    # the trained values arrive in the compute dtype already: the
    # masters live in the shard (grad_accum > 1 is refused here)
    grads_of = local_grads(fwd, compute_dtype, grad_accum)

    def grads_part(train_vals, aux_vals, x, y, key, scale):
        loss_val, muts, grads = grads_of(train_vals, aux_vals, x, y, key,
                                         scale)
        with jax.named_scope("grad_reduce"):
            if _prec.PRECISION_F32_GRAD_REDUCE:
                # cast BEFORE the collective: the ring reduction must run
                # f32 (the tightened DST004 contract, docs/precision.md)
                flat_g = _flatten_pad(grads, plan, jnp)
                g_sh = lax.psum_scatter(flat_g, axis, scatter_dimension=0,
                                        tiled=True) / k
            else:
                # the seam's broken spelling (tests only): reduce in bf16
                # and widen after — exactly what DST004 must catch
                flat_g = _flatten_pad(grads, plan, jnp, compute_dtype)
                g_sh = lax.psum_scatter(flat_g, axis, scatter_dimension=0,
                                        tiled=True).astype(jnp.float32) / k
            # global inf/nan verdict: every rank checks its owned shard,
            # pmin ANDs the flags (1.0 = every gradient element finite)
            fin = lax.pmin(
                jnp.isfinite(g_sh).all().astype(jnp.float32), axis)
            loss_val = lax.pmean(loss_val, axis)
            muts = tuple(lax.pmean(m.astype(jnp.float32), axis)
                         for m in muts)
        return g_sh, loss_val, muts, fin

    @jax.named_scope("optimizer_update")
    def update_part(train_vals, master_sh, state_leaves, g_sh, lr, t,
                    scale, good, skipped, fin):
        from ..ops import fused_optimizer as _fused

        if _prec.PRECISION_MASTER_F32:
            # the masters ARE the shard: each rank updates the f32
            # slice it owns; no flat f32 weight vector ever exists
            w_sh = master_sh
        else:
            # the seam's broken spelling (tests only): "masters"
            # re-derived from the bf16 params — the full flat f32
            # space materializes per rank and the master precision is
            # lost, which the bf16_zero1_train_step peak-HBM/precision
            # proof must catch (COST001 rc=2)
            flat_w = _flatten_pad(train_vals, plan, jnp)
            idx = lax.axis_index(axis)
            w_sh = lax.dynamic_slice(flat_w, (idx * shard,), (shard,))
        inv = (1.0 / scale).astype(jnp.float32)
        state = jax.tree_util.tree_unflatten(state_treedef,
                                             list(state_leaves))
        if _fused.fused_update_enabled() and _fused.supports(opt):
            # unscale + clip + update + select-skip as ONE kernel pass:
            # the loss-scale reciprocal and the finite flag ride the
            # SMEM scalar block (docs/fusion.md, docs/precision.md)
            new_w_sh, new_state = _fused.fused_optimizer_update(
                opt, 0, w_sh, g_sh, state, lr, t, inv_scale=inv,
                ok=fin)
        else:
            nw, ns = functional_optimizer_update(
                opt, 0, w_sh, g_sh * inv, state, lr, t)
            okb = fin > 0.0
            new_w_sh = jnp.where(okb, nw, w_sh)
            new_state = jax.tree_util.tree_map(
                lambda n, o: jnp.where(okb, n, o), ns, state)
        new_scale, new_good = _prec.loss_scale_update(scale, good,
                                                      fin > 0.0)
        new_skipped = skipped + (1 - fin.astype(jnp.int32))
        # cast BEFORE the gather: the reassembled params are bf16, so
        # the all-gather moves half the wire bytes and the gathered
        # param copy holds half the HBM of the f32 twin
        new_w_c = new_w_sh.astype(compute_dtype)
        if ZERO1_RUNTIME_ALL_GATHER:
            new_flat = lax.all_gather(new_w_c, axis, tiled=True)
        else:
            # the classic broken spelling (tests only; see build_parts)
            new_flat = jnp.concatenate([new_w_c] * k) if k > 1 \
                else new_w_c
        new_vals = _unflatten(new_flat, plan, jnp)
        return (new_vals, new_w_sh,
                tuple(jax.tree_util.tree_leaves(new_state)),
                new_scale, new_good, new_skipped)

    return grads_part, update_part


def build_replica_step(fwd, opt, plan, state_treedef,
                       compute_dtype=None, grad_accum=1):
    """One per-replica function composing both halves — the analysis
    spelling.  ``step(train_vals, state_leaves, aux_vals, x, y, key,
    lr, t) -> (loss, new_vals, new_state_leaves, muts)``; trace with
    ``jax.make_jaxpr(axis_env=[(plan.axis, plan.k)])``.

    Under a reduced ``compute_dtype`` the spelling grows the
    mixed-precision arguments instead (the :func:`build_parts`
    docstring): ``step(train_vals, master_sh, state_leaves, aux_vals,
    x, y, key, lr, t, scale, good, skipped) -> (loss, new_vals,
    new_master_sh, new_state_leaves, muts, new_scale, new_good,
    new_skipped)``."""
    import jax.numpy as jnp

    grads_part, update_part = build_parts(fwd, opt, plan, state_treedef,
                                          compute_dtype=compute_dtype,
                                          grad_accum=grad_accum)
    if compute_dtype is not None and \
            jnp.dtype(compute_dtype) != jnp.float32:
        def replica_step(train_vals, master_sh, state_leaves, aux_vals,
                         x, y, key, lr, t, scale, good, skipped):
            g_sh, loss_val, muts, fin = grads_part(
                train_vals, aux_vals, x, y, key, scale)
            (new_vals, new_master, new_states, new_scale, new_good,
             new_skipped) = update_part(
                train_vals, master_sh, state_leaves, g_sh, lr, t,
                scale, good, skipped, fin)
            return (loss_val, new_vals, new_master, new_states, muts,
                    new_scale, new_good, new_skipped)

        return replica_step

    def replica_step(train_vals, state_leaves, aux_vals, x, y, key,
                     lr, t):
        g_sh, loss_val, muts = grads_part(train_vals, aux_vals, x, y,
                                          key)
        new_vals, new_states = update_part(train_vals, state_leaves,
                                           g_sh, lr, t)
        return loss_val, new_vals, new_states, muts

    return replica_step


def build_runtime_fns(fwd, opt, plan, state_treedef, mesh,
                      compute_dtype=None, grad_accum=1):
    """``(grad_fn, update_fn)`` — the jitted ``shard_map`` programs the
    trainer dispatches each step.  ``grad_fn``'s flat-gradient output
    and the optimizer-state leaves are GLOBAL ``(padded,)`` arrays
    sharded ``P(axis)`` (each device holds its ``shard``-sized slice);
    params/aux/loss stay replicated; the batch shards over ``axis``.
    ``update_fn`` donates params, states and the gradient shard, so the
    update happens in place in HBM exactly like the fused step.

    Under a reduced ``compute_dtype`` the f32 master shard is an extra
    GLOBAL ``(padded,)`` ``P(axis)`` array threaded through ``update_fn``
    (donated in, returned out) and the loss-scale scalars ride
    replicated — the :func:`build_parts` mixed-precision signature."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from .ring_attention import _shard_map

    grads_part, update_part = build_parts(fwd, opt, plan, state_treedef,
                                          compute_dtype=compute_dtype,
                                          grad_accum=grad_accum)
    axis = plan.axis
    if compute_dtype is not None and \
            jnp.dtype(compute_dtype) != jnp.float32:
        grad_fn = jax.jit(_shard_map(
            grads_part, mesh,
            in_specs=(P(), P(), P(axis), P(axis), P(), P()),
            out_specs=(P(axis), P(), P(), P())))
        update_fn = jax.jit(_shard_map(
            update_part, mesh,
            in_specs=(P(), P(axis), P(axis), P(axis), P(), P(), P(),
                      P(), P(), P()),
            out_specs=(P(), P(axis), P(axis), P(), P(), P())),
            donate_argnums=(0, 1, 2, 3))
        return grad_fn, update_fn
    grad_fn = jax.jit(_shard_map(
        grads_part, mesh,
        in_specs=(P(), P(), P(axis), P(axis), P()),
        out_specs=(P(axis), P(), P())))
    update_fn = jax.jit(_shard_map(
        update_part, mesh,
        in_specs=(P(), P(axis), P(axis), P(), P()),
        out_specs=(P(), P(axis))), donate_argnums=(0, 1, 2))
    return grad_fn, update_fn


def reassemble_state(shard_arrays, total):
    """Concatenate one state leaf's per-rank shards (save-time order)
    and truncate the padding tail -> the exact ``(total,)`` full leaf.
    Lossless: the tail is provably zero (module docstring)."""
    full = _np.concatenate([_np.asarray(a).ravel() for a in shard_arrays])
    if full.shape[0] < total:
        raise ValueError("shards hold %d elements, need %d"
                         % (full.shape[0], total))
    return full[:total]


def reshard_full(full, k):
    """Deterministically re-shard one full ``(total,)`` leaf for a fleet
    of size ``k``: zero-pad to the new K-multiple and split into K equal
    contiguous shards.  ``reassemble_state(reshard_full(x, k), len(x))``
    is the identity for every k — the 1→2→4→1 bitwise round-trip."""
    full = _np.asarray(full).ravel()
    total = full.shape[0]
    padded = -(-total // int(k)) * int(k)
    if padded != total:
        full = _np.concatenate(
            [full, _np.zeros((padded - total,), full.dtype)])
    shard = padded // int(k)
    return [full[r * shard:(r + 1) * shard] for r in range(int(k))]
