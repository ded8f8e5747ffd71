"""Mixed-precision policy: bf16 compute with f32 masters + dynamic loss
scaling (ROADMAP item 5a, arxiv 2004.13336's motivating layout).

One module owns every precision decision so the three trainer spellings
(replicated, ``zero=1``, PR-14 mesh) and the analysis tier agree:

- **dtype policy** — :func:`resolve_dtype` maps the trainer's
  ``dtype=`` knob to a compute dtype.  Under ``bf16`` the *params and
  activations* are bfloat16; the f32 **master weights** exist only as
  the ZeRO-1 flat shard (``parallel/zero.py`` keeps them as a state
  leaf, physically ``P(axis)``-sharded — they never materialize
  unsharded) or, for the replicated spelling, as the ordinary f32
  ``train_vals`` cast to bf16 at the forward boundary.
- **gradient reduction dtype** — gradients are cast to f32 BEFORE the
  cross-replica collective (psum / psum_scatter).  A bf16 ring
  reduction loses ~3 decimal digits per hop; the tightened DST004 rule
  (``analysis/dist_lint.py``) fails the gate when a sub-f32 float is
  reduced over the data axis.  ``PRECISION_F32_GRAD_REDUCE`` is the
  mutation seam proving that gate bites.
- **dynamic loss scaling** — the classic grow/backoff machine
  (:func:`loss_scale_update`): multiply the loss by ``scale`` so bf16
  gradients don't flush to zero, unscale inside the fused optimizer
  kernel (``ops/fused_optimizer.py`` reads ``[lr, inv_scale, ok]`` from
  SMEM — unscale+clip+update stays ONE kernel pass), skip the step and
  halve the scale on inf/nan, double it after ``GROWTH_INTERVAL``
  consecutive finite steps.  Skipped steps are select-skips: the kernel
  writes back the OLD weights/state, so a skipped step is a true no-op.
- **telemetry** — :func:`record_loss_scale` publishes the live scale
  (``mxtpu_loss_scale`` gauge) and the skipped-step total
  (``mxtpu_loss_scale_skipped_steps_total`` counter) through the PR-9
  registry (docs/observability.md).

``PRECISION_MASTER_F32`` is the budget-gate mutation seam
(``parallel/zero.py`` ``ZERO1_RUNTIME_ALL_GATHER`` discipline): flipping
it False makes the bf16 ZeRO-1 update re-derive its "masters" from the
bf16 params via the full flat f32 spelling — masters materialize
unsharded and the ``bf16_zero1_train_step`` row's pinned peak-HBM drop
vs the f32 twin fails (COST001, rc=2; tests/test_precision.py,
subprocess).  Production code never touches either seam.
"""
from __future__ import annotations

__all__ = ["PRECISION_MASTER_F32", "PRECISION_F32_GRAD_REDUCE",
           "LOSS_SCALE_INIT", "GROWTH_FACTOR", "BACKOFF_FACTOR",
           "GROWTH_INTERVAL", "MAX_SCALE", "MIN_SCALE", "resolve_dtype",
           "is_reduced", "init_loss_scale", "all_finite",
           "loss_scale_update", "record_loss_scale"]

# budget-gate mutation seams (module docstring) — flipped only by tests
PRECISION_MASTER_F32 = True
PRECISION_F32_GRAD_REDUCE = True

# the loss-scale state machine's pinned constants (docs/precision.md)
LOSS_SCALE_INIT = 2.0 ** 15
GROWTH_FACTOR = 2.0
BACKOFF_FACTOR = 0.5
GROWTH_INTERVAL = 200
MAX_SCALE = 2.0 ** 24
MIN_SCALE = 1.0

_ALIASES = {"f32": "float32", "fp32": "float32", "float32": "float32",
            "bf16": "bfloat16", "bfloat16": "bfloat16"}


def resolve_dtype(spec):
    """The trainer's ``dtype=`` knob -> a jnp dtype (``float32`` /
    ``bfloat16``).  ``None`` means float32 (the historical default)."""
    import jax.numpy as jnp

    if spec is None:
        return jnp.float32
    if isinstance(spec, str):
        name = _ALIASES.get(spec.lower())
        if name is None:
            raise ValueError("dtype must be one of %s, got %r"
                             % (sorted(set(_ALIASES)), spec))
        return jnp.dtype(name)
    dt = jnp.dtype(spec)
    if dt not in (jnp.dtype("float32"), jnp.dtype("bfloat16")):
        raise ValueError("dtype must be float32 or bfloat16, got %r"
                         % (spec,))
    return dt


def is_reduced(dtype):
    """True when ``dtype`` is a sub-f32 compute dtype (loss scaling and
    master weights apply)."""
    import jax.numpy as jnp

    return jnp.dtype(dtype) == jnp.dtype("bfloat16")


def _to_compute(v, dtype):
    """``v`` in the compute ``dtype`` if it is a floating leaf; integer
    leaves (labels, token ids) stay put.  The one cast every tier's step
    makes at its forward boundary, under the program scope
    ``compute_cast`` (docs/observability.md "Program scopes"): the
    masters' casts, their transposes in the backward pass, the batch's."""
    import jax
    import jax.numpy as jnp

    if hasattr(v, "dtype") and jnp.issubdtype(v.dtype, jnp.floating):
        with jax.named_scope("compute_cast"):
            return v.astype(dtype)
    return v


def init_loss_scale(init=LOSS_SCALE_INIT):
    """``(scale, good_steps)`` — the device-resident loss-scale state:
    f32 scalar scale, i32 consecutive-finite-step counter."""
    import jax.numpy as jnp

    return (jnp.asarray(init, jnp.float32), jnp.asarray(0, jnp.int32))


def all_finite(leaves):
    """Traced scalar bool: every element of every leaf is finite.  The
    per-step inf/nan probe the loss-scale machine keys on; cheap (one
    O(n) reduction already fused into the grad pass by XLA)."""
    import jax.numpy as jnp

    leaves = list(leaves)
    if not leaves:
        return jnp.asarray(True)
    flags = [jnp.isfinite(leaf).all() for leaf in leaves]
    out = flags[0]
    for f in flags[1:]:
        out = jnp.logical_and(out, f)
    return out


def loss_scale_update(scale, good_steps, grads_finite,
                      growth_factor=GROWTH_FACTOR,
                      backoff_factor=BACKOFF_FACTOR,
                      growth_interval=GROWTH_INTERVAL,
                      max_scale=MAX_SCALE, min_scale=MIN_SCALE):
    """One tick of the grow/backoff machine (pure, traced):

    - finite grads: ``good_steps += 1``; after ``growth_interval``
      consecutive finite steps the scale doubles (capped at
      ``max_scale``) and the counter resets;
    - non-finite grads: the step is skipped, the scale halves (floored
      at ``min_scale``), the counter resets.

    Returns ``(new_scale, new_good_steps)``.  The caller derives
    "skipped" from ``grads_finite`` itself (see
    ``DataParallelTrainer``'s skipped-step counter)."""
    import jax.numpy as jnp

    scale = jnp.asarray(scale, jnp.float32)
    good = jnp.asarray(good_steps, jnp.int32)
    fin = jnp.asarray(grads_finite, bool)
    grown_now = jnp.logical_and(fin, good + 1 >= growth_interval)
    new_scale = jnp.where(
        fin,
        jnp.where(grown_now,
                  jnp.minimum(scale * growth_factor, max_scale), scale),
        jnp.maximum(scale * backoff_factor, min_scale))
    new_good = jnp.where(jnp.logical_and(fin, jnp.logical_not(grown_now)),
                         good + 1, jnp.asarray(0, jnp.int32))
    return new_scale, new_good


def record_loss_scale(scale, skipped_delta=0, run_id=None):
    """Publish the live scale and any newly-skipped steps through the
    telemetry registry (host values — call outside traced code)."""
    from .telemetry.metrics import registry

    labels = {"run_id": run_id} if run_id else {}
    registry().gauge(
        "mxtpu_loss_scale",
        "current dynamic loss scale (mixed-precision training)"
    ).set(float(scale), **labels)
    if skipped_delta:
        registry().counter(
            "mxtpu_loss_scale_skipped_steps_total",
            "optimizer steps skipped on non-finite gradients"
        ).inc(int(skipped_delta), **labels)
