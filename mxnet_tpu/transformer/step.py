"""The multi-axis train step, spelled ONCE per replica.

``build_parts`` produces the two halves of the
``data × model × sequence × pipe`` training step over any
:class:`~mxnet_tpu.transformer.model.MeshProgram`:

- ``grads_part``: forward + backward on the local (batch, token) chunk
  — the model/sequence collectives live inside the program's
  ``loss_replica`` — then the step's ONE gradient exchange: every
  parameter gradient is ``pmean``'d over the plan's **batch axes**
  (``data`` and ``sequence``; model-sharded params keep their per-shard
  gradients — reducing them over ``model`` would mix unrelated shard
  coordinates, DST006; under ``pipeline=K`` only the pipe-replicated
  params are additionally psum-completed over ``pipe``, never the
  stage-local stacks — DST012), and under ``zero=1`` the flat LOCAL
  gradient is
  additionally reduce-scattered over ``data`` (arxiv 2004.13336 composed
  multiplicatively with the tensor/sequence sharding).
- ``update_part``: the optimizer applied shard-locally through a
  caller-supplied ``apply_update`` (the trainer passes the real gluon
  ``Optimizer.update`` via ``functional_optimizer_update``; the budget
  fixture passes an inline SGD+momentum), all-gathering the flat params
  back over ``data`` under ``zero=1`` (the DST007 pair).

Used two ways so runtime and analysis can never drift (the
``parallel/zero.py`` discipline): ``build_replica_step`` composes the
parts; ``build_runtime_fn`` jits the composition as ONE ``shard_map``
program over the plan's mesh, and the same composition is traced for
``jax.make_jaxpr(axis_env=plan.axis_env())`` — the
``tp_transformer_train_step`` budget tape and ``trainer.mesh_report()``.
"""
from __future__ import annotations

import numpy as _np

__all__ = ["TPZeroPlan", "build_parts", "build_replica_step",
           "build_runtime_fn", "sgd_momentum_update"]


class TPZeroPlan:
    """ZeRO-1 flat layout over the LOCAL parameter space of one model
    rank: local shards raveled f32 in ``param_names`` order, padded to
    the data-axis size K.  Because model-sharded params are replicated
    over ``data``, sharding their optimizer state over ``data`` is
    exactly the ZeRO-1 story, per model rank — the two shardings
    compose multiplicatively."""

    def __init__(self, program, k_data):
        self.k = int(k_data)
        self.names = list(program.param_names)
        self.local_shapes = [program.local_shape(n) for n in self.names]
        self.sizes = [int(_np.prod(s)) if s else 1
                      for s in self.local_shapes]
        self.total = int(sum(self.sizes))
        self.padded = -(-self.total // self.k) * self.k
        self.shard = self.padded // self.k

    def describe(self):
        return {"k": self.k, "total": self.total, "padded": self.padded,
                "shard": self.shard}


def sgd_momentum_update(momentum=0.9):
    """The budget fixture's inline elementwise optimizer:
    ``apply_update(i, w, g, state_leaves, lr, t) -> (new_w, new_leaves)``
    with one momentum leaf per parameter — numerically the gluon
    ``sgd`` rule the runtime trainer applies, spelled without the
    optimizer registry so the fixture stays dependency-light."""
    mu = float(momentum)

    def apply_update(_i, w, g, state_leaves, lr, _t):
        (m,) = state_leaves
        new_m = mu * m + g
        return w - lr * new_m, (new_m,)

    return apply_update


def _flatten_pad(vals, plan, jnp):
    parts = [v.ravel().astype(jnp.float32) for v in vals]
    pad = plan.padded - plan.total
    if pad:
        parts.append(jnp.zeros((pad,), jnp.float32))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def _unflatten(flat, plan):
    out, off = [], 0
    for shape, size in zip(plan.local_shapes, plan.sizes):
        out.append(flat[off:off + size].reshape(shape))
        off += size
    return tuple(out)


def build_parts(program, apply_update, state_leaf_counts, zero=0,
                zero_plan=None, compute_dtype=None):
    """``(grads_part, update_part)`` over LOCAL shards (the ``shard_map``
    / ``axis_env`` view).  ``state_leaf_counts[i]`` is parameter ``i``'s
    optimizer-state leaf count (flat leaves concatenated across params in
    order); under ``zero=1`` every leaf is instead one flat
    ``(shard,)``-sized slice of the :class:`TPZeroPlan` space.

    ``compute_dtype`` (mixed precision, docs/precision.md): the mesh
    tier keeps its params f32 — they ARE the masters — and casts params
    + batch to the compute dtype at the loss boundary, so activations
    run bf16 while gradients come back f32 through the cast transpose
    and every collective reduces f32 (the tightened DST004 contract).
    No loss scaling here: bf16 carries f32's 8-bit exponent, so grads
    cannot flush to zero the way f16's 5-bit exponent loses them."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..precision import _to_compute

    plan = program.plan
    batch_axes = plan.batch_axes()
    if zero and zero_plan is None:
        raise ValueError("zero=1 needs a TPZeroPlan")
    reduced = (compute_dtype is not None
               and jnp.dtype(compute_dtype) != jnp.float32)

    def grads_part(train_vals, x, y, key):
        if reduced:
            x_c = _to_compute(x, compute_dtype)

            def loss_of(tv):
                return program.loss_replica(
                    tuple(_to_compute(w, compute_dtype) for w in tv),
                    x_c, y, key)

            loss, grads = jax.value_and_grad(loss_of)(tuple(train_vals))
            loss = loss.astype(jnp.float32)
            # f32 already via the cast transpose — spelled out so the
            # wire contract survives a program whose loss math changes
            grads = tuple(g.astype(jnp.float32) for g in grads)
        else:
            loss, grads = jax.value_and_grad(program.loss_replica)(
                tuple(train_vals), x, y, key)
        # the collectives the program spells itself carry one scope
        # (docs/observability.md "Program scopes")
        with jax.named_scope("grad_reduce"):
            return _reduce(grads, loss)

    def _reduce(grads, loss):
        if plan.present("pipe"):
            # the ONE pipe-axis exchange: complete the pipe-replicated
            # params' partial grads; stage-local blk_* grads pass
            # through (reducing them over pipe mixes layers — DST012)
            from ..parallel.pipeline import reduce_replicated_grads
            grads = reduce_replicated_grads(
                grads, program.param_names, program.pipe_replicated)
        if batch_axes:
            loss = lax.pmean(loss, batch_axes)
        if zero:
            # sequence ranks hold partial grads of the same shard: mean
            # them first, then scatter the data axis so each data rank
            # lands exactly its owned slice of the flat local space
            if plan.present("sequence"):
                grads = tuple(lax.pmean(g, "sequence") for g in grads)
            flat_g = _flatten_pad(grads, zero_plan, jnp)
            if plan.present("data"):
                g_out = lax.psum_scatter(
                    flat_g, "data", scatter_dimension=0,
                    tiled=True) / zero_plan.k
            else:
                g_out = flat_g
            return g_out, loss
        if batch_axes:
            grads = tuple(lax.pmean(g, batch_axes) for g in grads)
        return tuple(grads), loss

    @jax.named_scope("optimizer_update")
    def update_part(train_vals, state_leaves, grads, lr, t):
        if zero:
            flat_w = _flatten_pad(train_vals, zero_plan, jnp)
            if plan.present("data"):
                idx = lax.axis_index("data")
                w_sh = lax.dynamic_slice(
                    flat_w, (idx * zero_plan.shard,), (zero_plan.shard,))
            else:
                w_sh = flat_w
            new_w_sh, new_leaves = apply_update(
                0, w_sh, grads, tuple(state_leaves), lr, t)
            if plan.present("data"):
                new_flat = lax.all_gather(new_w_sh, "data", tiled=True)
            else:
                new_flat = new_w_sh
            return _unflatten(new_flat, zero_plan), tuple(new_leaves)
        new_vals, new_leaves, off = [], [], 0
        for i, (w, g) in enumerate(zip(train_vals, grads)):
            n = state_leaf_counts[i]
            leaves = tuple(state_leaves[off:off + n])
            off += n
            nw, nl = apply_update(i, w, g, leaves, lr, t)
            new_vals.append(nw)
            new_leaves.extend(nl)
        return tuple(new_vals), tuple(new_leaves)

    return grads_part, update_part


def build_replica_step(program, apply_update, state_leaf_counts, zero=0,
                       zero_plan=None, compute_dtype=None):
    """Both halves composed into one per-replica function — the analysis
    spelling.  ``step(train_vals, state_leaves, x, y, key, lr, t) ->
    (loss, new_vals, new_state_leaves)``; trace with
    ``jax.make_jaxpr(axis_env=program.plan.axis_env())``."""
    grads_part, update_part = build_parts(
        program, apply_update, state_leaf_counts, zero=zero,
        zero_plan=zero_plan, compute_dtype=compute_dtype)

    def replica_step(train_vals, state_leaves, x, y, key, lr, t):
        grads, loss = grads_part(train_vals, x, y, key)
        new_vals, new_leaves = update_part(train_vals, state_leaves,
                                           grads, lr, t)
        return loss, new_vals, new_leaves

    return replica_step


def build_runtime_fn(program, apply_update, state_leaf_counts, mesh,
                     state_specs, zero=0, zero_plan=None,
                     compute_dtype=None):
    """The jitted ``shard_map`` program the trainer dispatches each step:
    :func:`build_replica_step`'s composition of both halves, ``step_fn(
    train_vals, state_leaves, x, y, key, lr, t) -> (loss, new_vals,
    new_state_leaves)``.  Params ride their ``program.partition_spec``;
    the batch rides ``plan.batch_spec()``; optimizer-state leaves ride
    ``state_specs`` (per-param specs, or the flat ``P(("model", "data"))``
    space under ``zero=1``).  Params and states are donated, so the update
    happens in place in HBM, and the gradients are the program's own
    temporaries: a step that is enqueued ahead allocates nothing (as two
    programs, each enqueued step pinned a gradient set of the parameters'
    size, and the run-ahead filled the chip with them: PERF.md, PR 28)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..parallel.ring_attention import _shard_map

    replica_step = build_replica_step(
        program, apply_update, state_leaf_counts, zero=zero,
        zero_plan=zero_plan, compute_dtype=compute_dtype)
    param_specs = tuple(program.partition_spec(n)
                        for n in program.param_names)
    batch_spec = program.plan.batch_spec()
    state_specs = tuple(state_specs)
    return jax.jit(_shard_map(
        replica_step, mesh,
        in_specs=(param_specs, state_specs, batch_spec, batch_spec, P(),
                  P(), P()),
        out_specs=(P(), param_specs, state_specs)),
        donate_argnums=(0, 1))
