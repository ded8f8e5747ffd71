"""The gluon tiers' training step, spelled once as parts.

``DataParallelTrainer`` trains a gluon block on three tiers (replicated,
kvstore split, ZeRO-1) and analyses each of them without hardware.  All of
them begin with the same body: forward, loss, backward on the local batch.
:func:`local_grads` is that body's one spelling; ``parallel/zero.py``
builds its reduce-scatter half on it, and :func:`build_parts` the
replicated tier's two halves:

- ``grads_part``: :func:`local_grads`, plus the ``pmean`` of gradients,
  loss and BatchNorm statistics over ``axis`` where one is given (the
  per-replica view the DST lint verifies; under ``jax.jit`` +
  ``NamedSharding`` the compiler inserts the same reduction, because the
  loss is a mean over the batch-sharded axis);
- ``update_part``: the trainer's optimizer update, with the finite check
  and the loss-scale tick where the compute dtype is reduced.

:func:`build_replica_step` composes the two; :func:`build_runtime_fn`
jits that composition (the one program the replicated tier dispatches)
and :func:`build_split_fns` jits each half with the kvstore's flat vector
between them.  The trainer's ``cost_report``/``shard_report``/
``fusion_report`` and ``analysis/dist_lint.py`` trace the same
composition, so what is analysed is what runs (the ``transformer/step.py``
discipline).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import precision as _precision
from .functional import accumulate_grads

__all__ = ["local_grads", "reduce_grads", "build_parts",
           "build_replica_step", "build_runtime_fn", "build_split_fns"]


def local_grads(fwd, compute_dtype=None, grad_accum=1, cast_trained=False):
    """Forward + backward on the local batch, as a pure function
    ``(train_vals, aux_vals, x, y, key) -> (loss, muts, grads)`` over the
    functionalized forward ``fwd`` (``functional.functionalize_forward``).

    ``grad_accum > 1`` splits the batch into that many microbatches and
    left-fold sums their gradients (``functional.accumulate_grads``);
    loss, gradients and mutated statistics come back as the means.

    A reduced ``compute_dtype`` (docs/precision.md) adds a trailing
    ``scale`` argument: the batch and the floating aux values enter the
    forward in the compute dtype (f32 inputs would promote the
    activations back and the bytes win evaporates), the SCALED loss
    drives the backward so bf16 gradients don't flush, and the raw f32
    loss is what is returned.  ``cast_trained`` says whose the trained
    values are: the replicated tier passes its f32 masters and casts them
    inside the differentiated function (gradients come back f32 through
    the cast's transpose); ZeRO-1 passes values that are in the compute
    dtype already, its masters live in the shard.  The tier that builds
    the step decides; it is no user option."""
    n_acc = int(grad_accum or 1)
    if compute_dtype is not None:
        if n_acc > 1:
            raise ValueError("grad_accum is not supported with a reduced "
                             "compute dtype (see DataParallelTrainer)")

        def cast(v):
            return _precision._to_compute(v, compute_dtype)

        def scaled_grads(train_vals, aux_vals, x, y, key, scale):
            x_c = cast(x)
            aux_c = tuple(cast(a) for a in aux_vals)

            def loss_of(tv):
                if cast_trained:
                    tv = tuple(cast(w) for w in tv)
                outs, muts = fwd(tv, aux_c, (x_c, y), key)
                raw = outs[0].astype(jnp.float32)
                return raw * scale, (raw, muts)

            (_, (loss_val, muts)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(train_vals)
            return loss_val, muts, grads

        return scaled_grads

    def grads_of(train_vals, aux_vals, x, y, key):
        def grad_of(tv, xi, yi):
            def loss_of(t_):
                outs, muts = fwd(t_, aux_vals, (xi, yi), key)
                return outs[0], muts
            return jax.value_and_grad(loss_of, has_aux=True)(tv)

        if n_acc == 1:
            (loss_val, muts), grads = grad_of(train_vals, x, y)
            return loss_val, muts, grads
        grads_sum, loss_sum, muts_stack = accumulate_grads(
            grad_of, train_vals, x, y, n_acc)
        grads = tuple(g / n_acc for g in grads_sum)
        return (loss_sum / n_acc,
                tuple(m.mean(axis=0) for m in muts_stack), grads)

    return grads_of


def reduce_grads(grads, axis):
    """Cross-replica gradient mean over the data axis: the replicated
    step's ONE reduction point, explicit in the per-replica spelling.
    Removing this call is exactly the "gradient psum removed" bug class:
    DST001 fires per parameter (tests/test_analysis.py)."""
    with jax.named_scope("grad_reduce"):
        return tuple(jax.lax.pmean(g, axis) for g in grads)


def build_parts(fwd, apply_update, axis=None, compute_dtype=None,
                grad_accum=1):
    """``(grads_part, update_part)`` of the replicated tier.

    - ``grads_part(train_vals, aux_vals, x, y, key[, scale]) -> (loss,
      muts, grads)``: :func:`local_grads` over the f32 masters; with
      ``axis`` the gradients, the reported loss and the BatchNorm batch
      statistics are ``pmean``'d over it (all three are global under
      GSPMD).  Reduced, the statistics are widened to f32 first: the
      collective reduces f32 (the tightened DST004 contract).
    - ``update_part(train_vals, states, grads, lr, t[, scale, good,
      skipped]) -> (new_vals, new_states[, new_scale, new_good,
      new_skipped])``: ``apply_update`` is the trainer's
      ``_apply_groups``.  Reduced, the update unscales and select-skips
      on the global finite flag (one kernel pass when fused) and the
      loss-scale machine ticks (docs/precision.md)."""
    grads_of = local_grads(fwd, compute_dtype, grad_accum,
                           cast_trained=True)
    reduced = compute_dtype is not None

    def grads_part(train_vals, aux_vals, x, y, key, *scale):
        loss_val, muts, grads = grads_of(train_vals, aux_vals, x, y, key,
                                         *scale)
        if reduced:
            muts = tuple(m.astype(jnp.float32) for m in muts)
        if axis is not None:
            grads = reduce_grads(grads, axis)
            loss_val = jax.lax.pmean(loss_val, axis)
            muts = tuple(jax.lax.pmean(m, axis) for m in muts)
        return loss_val, muts, grads

    if not reduced:
        return grads_part, apply_update

    def update_part(train_vals, states, grads, lr, t, scale, good,
                    skipped):
        fin = _precision.all_finite(grads)
        inv = (1.0 / scale).astype(jnp.float32)
        new_vals, new_states = apply_update(
            train_vals, states, grads, lr, t,
            inv_scale=inv, ok=fin.astype(jnp.float32))
        new_scale, new_good = _precision.loss_scale_update(scale, good,
                                                           fin)
        new_skipped = skipped + (1 - fin.astype(jnp.int32))
        return new_vals, new_states, new_scale, new_good, new_skipped

    return grads_part, update_part


def build_replica_step(fwd, apply_update, axis=None, compute_dtype=None,
                       grad_accum=1):
    """Both halves composed: ``pure_step(train_vals, states, aux_vals, x,
    y, key, lr, t[, scale, good, skipped]) -> (loss, new_vals,
    new_states, muts[, new_scale, new_good, new_skipped])``.  With
    ``axis`` it is the step seen from one shard of the data axis, traced
    with ``jax.make_jaxpr(axis_env=[(axis, K)])``; without, the step
    :func:`build_runtime_fn` jits."""
    grads_part, update_part = build_parts(
        fwd, apply_update, axis=axis, compute_dtype=compute_dtype,
        grad_accum=grad_accum)

    if compute_dtype is None:
        def pure_step(train_vals, states, aux_vals, x, y, key, lr, t):
            loss_val, muts, grads = grads_part(train_vals, aux_vals, x, y,
                                               key)
            new_vals, new_states = update_part(train_vals, states, grads,
                                               lr, t)
            return loss_val, new_vals, new_states, muts

        return pure_step

    def pure_step(train_vals, states, aux_vals, x, y, key, lr, t, scale,
                  good, skipped):
        loss_val, muts, grads = grads_part(train_vals, aux_vals, x, y, key,
                                           scale)
        new_vals, new_states, new_scale, new_good, new_skipped = \
            update_part(train_vals, states, grads, lr, t, scale, good,
                        skipped)
        return (loss_val, new_vals, new_states, muts, new_scale, new_good,
                new_skipped)

    return pure_step


def build_runtime_fn(fwd, apply_update, compute_dtype=None, grad_accum=1):
    """The one jitted program the replicated tier dispatches each step
    (``jit(pure_step)``), parameters and optimizer states donated so the
    update happens in place in HBM."""
    return jax.jit(build_replica_step(
        fwd, apply_update, compute_dtype=compute_dtype,
        grad_accum=grad_accum), donate_argnums=(0, 1))


def build_split_fns(fwd, apply_update, sizes, num_workers):
    """``(grad_fn, update_fn)``: the kvstore tier's two jitted programs.
    The gradients cross the process boundary through the kvstore between
    them (reference: executor backward -> kv.push, kv.pull -> updater,
    python/mxnet/model.py:157) as ONE flat f32 vector with the loss
    scalar riding along; ``sizes`` are the trained parameters' element
    counts in order."""
    grads_part, update_part = build_parts(fwd, apply_update)
    scale = 1.0 / num_workers

    def pure_grads(train_vals, aux_vals, x, y, key):
        loss_val, muts, grads = grads_part(train_vals, aux_vals, x, y,
                                           key)
        # flatten inside the jit: the host sees one fused vector ready
        # to push
        flat = jnp.concatenate(
            [g.ravel().astype(jnp.float32) for g in grads]
            + [loss_val.reshape(1).astype(jnp.float32)])
        return flat, muts

    def pure_update(train_vals, states, flat_sum, lr, t):
        mean = flat_sum * scale
        grads, off = [], 0
        for tv, n in zip(train_vals, sizes):
            grads.append(mean[off:off + n].reshape(tv.shape)
                         .astype(tv.dtype))
            off += n
        new_vals, new_states = update_part(train_vals, states,
                                           tuple(grads), lr, t)
        # the global-batch mean loss comes back out of the update jit, so
        # every rank's callbacks see the number the single-process run
        # would (a local loss would diverge across ranks)
        return mean[-1], new_vals, new_states

    return (jax.jit(pure_grads),
            jax.jit(pure_update, donate_argnums=(0, 1)))
