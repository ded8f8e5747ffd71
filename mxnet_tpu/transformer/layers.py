"""Per-replica tensor-parallel transformer layers (GSPMD spelled out).

Every function here is the **per-replica** view of one Megatron-style
sharded layer (arxiv 1810.09868's annotations, written as the explicit
``shard_map`` program the compiler would derive): inputs are LOCAL
shards, collectives are explicit ``lax`` calls over the plan's collapsed
axes, and a :class:`~mxnet_tpu.parallel.mesh.MeshPlan` with a size-1
``model`` axis produces **zero** model collectives — the replicated
spelling and the sharded spelling are the same code.

The sharding grammar (docs/transformer.md has the full table):

- **column-parallel** (out-feature dim over ``model``): no collective —
  the activation comes out model-sharded (QKV heads, MLP ``w1``).
- **row-parallel** (in-feature dim over ``model``): each rank's matmul
  produces a partial sum; :func:`row_parallel_out` completes it with the
  ``psum`` over ``model`` (attention output proj, MLP ``w2``).  This is
  the layer the whole proof hangs on — see the seam below.
- **vocab-parallel** (vocab dim over ``model``): the embedding gathers
  from the local vocab slice and psums the misses away; the logit/loss
  side never materializes the full vocab — max/sum-exp/picked-logit are
  completed by ``pmax``/``psum`` over ``model``
  (:func:`vocab_parallel_cross_entropy`, the "final-logit psum").

``TP_ROW_PSUM`` is the **mutation seam** (the ``parallel/zero.py``
``ZERO1_RUNTIME_ALL_GATHER`` discipline): flipping it False deletes the
row-parallel output psum — the classic "forgot the all-reduce" bug where
every rank trains on its own partial activations — and the
``tp_transformer_train_step`` budget gate must fail rc=2 with the
pending-partial-sum DST001 named per parameter
(tests/test_transformer.py, subprocess).  Production code never touches
it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from .ssm import PROJECTION

__all__ = ["TP_ROW_PSUM", "layer_norm", "rms_norm", "gated_mlp",
           "column_parallel_dense", "row_parallel_out", "copy_to_model",
           "complete_psum",
           "vocab_parallel_embedding", "vocab_parallel_cross_entropy",
           "sequence_offset"]

# runtime+analysis mutation seam (see module docstring) — tests only
TP_ROW_PSUM = True


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _complete_psum(x, axis):
    return lax.psum(x, axis)


def _complete_psum_fwd(x, axis):
    return lax.psum(x, axis), None


def _complete_psum_bwd(axis, _res, g):
    # Megatron's ``g`` operator: the psum completes per-rank partials
    # into ONE replicated value consumed by ONE (replicated) downstream
    # loss, so each rank's partial receives exactly the replicated
    # cotangent.  jax's default psum transpose (psum again) would
    # instead differentiate Σ_ranks L_r and scale every upstream path
    # by the axis size per crossed psum.
    return (g,)


_complete_psum.defvjp(_complete_psum_fwd, _complete_psum_bwd)


def complete_psum(x, plan, axis="model"):
    """Sum per-rank partials over ``axis`` into the replicated value
    (identity backward — module docstring); collapses to identity when
    the axis is absent from the plan."""
    if plan.present(axis):
        return _complete_psum(x, axis)
    return x


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _model_region(x, axis):
    return x


def _model_region_fwd(x, axis):
    return x, None


def _model_region_bwd(axis, _res, g):
    return (lax.psum(g, axis),)


_model_region.defvjp(_model_region_fwd, _model_region_bwd)


def copy_to_model(x, plan):
    """Megatron's ``f`` operator: identity forward, ``psum`` over
    ``model`` backward.  A replicated activation entering a
    column-parallel region gets per-shard partial cotangents (each rank
    back-propagates only its feature/head slice); this completes them —
    without it the grads of every replicated parameter upstream (LNs,
    embeddings) silently diverge across model ranks after one step."""
    if plan.present("model"):
        return _model_region(x, "model")
    return x


def layer_norm(x, scale, bias, eps=1e-5):
    """LayerNorm over the (replicated) feature dim — no collectives.

    The layernorm→dense chain is a top-ranked mxfuse candidate
    (docs/fusion.md): when the fused kernel is enabled (TPU with a
    lane-aligned f32 feature dim, or ``MXTPU_FUSED_LAYERNORM=1``), the
    normalization runs as ONE Pallas pass over HBM instead of the
    mean/var/normalize eqn chain; numerics match this spelling to float
    tolerance (tests/test_fusion.py) and the backward recomputes
    statistics flash-style."""
    from ..ops import fused_optimizer as _fused
    if _fused.fused_layernorm_enabled(feature_dim=x.shape[-1],
                                      dtype=x.dtype):
        return _fused.fused_layer_norm(x, scale, bias, eps)
    mu = x.mean(axis=-1, keepdims=True)
    var = ((x - mu) ** 2).mean(axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * scale + bias


def rms_norm(x, weight, eps):
    """``x / sqrt(mean(x^2) + eps) * weight``, the mean in float32."""
    xf = x.astype(jnp.float32)
    xf = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                        + eps)
    return (xf * weight.astype(jnp.float32)).astype(x.dtype)


def gated_mlp(lp, x):
    """``(silu(a) * b) W_out`` with ``[a, b] = x W_in``.  ``@ W_out``'s
    result carries no tag: it is the layer's output, which nothing in the
    layer's backward pass reads."""
    a, b = jnp.split(checkpoint_name(x @ lp["mlp_in"], PROJECTION), 2,
                     axis=-1)
    return (jax.nn.silu(a) * b) @ lp["mlp_out"]


def column_parallel_dense(x, w_local, b_local=None):
    """``x @ W`` with W column-sharded over ``model``: the contraction
    dim is replicated, so there is no collective — the output's feature
    dim is the local shard (heads, MLP hidden)."""
    y = x @ w_local
    if b_local is not None:
        y = y + b_local
    return y


def row_parallel_out(partial, plan, bias=None):
    """Complete a row-parallel matmul's partial sum over ``model`` and
    add the (replicated) bias AFTER the reduction — the one collective
    of the attention output / MLP down projection, and the seam the
    budget gate kills (module docstring)."""
    if plan.present("model") and TP_ROW_PSUM:
        partial = _complete_psum(partial, "model")
    if bias is not None:
        partial = partial + bias
    return partial


def sequence_offset(plan, t_local):
    """Global position of this replica's first token: the sequence axis
    shards tokens in order, so chunk ``s`` starts at ``s * t_local``."""
    if plan.present("sequence"):
        return lax.axis_index("sequence") * t_local
    return 0


def vocab_parallel_embedding(table_local, ids, plan):
    """Gather rows of a vocab-sharded ``(V/Km, d)`` table for GLOBAL ids:
    out-of-shard ids gather row 0 and are masked to zero, then one psum
    over ``model`` fills every position from whichever rank owns it."""
    if not plan.present("model"):
        return jnp.take(table_local, ids, axis=0)
    v_local = table_local.shape[0]
    off = lax.axis_index("model") * v_local
    local = ids - off
    in_range = (local >= 0) & (local < v_local)
    emb = jnp.take(table_local, jnp.where(in_range, local, 0), axis=0)
    emb = emb * in_range[..., None].astype(emb.dtype)
    return _complete_psum(emb, "model")


def vocab_parallel_cross_entropy(logits_local, labels, plan):
    """Per-token causal-LM loss over vocab-sharded logits
    ``(..., V/Km)`` without ever materializing the full vocab row:
    the stable logsumexp's max rides ``pmax``, its sum-of-exponentials
    and the picked target logit ride ``psum`` — the "final-logit psum"
    trio over ``model``.  Labels are GLOBAL vocab ids."""
    # the logsumexp max is numerical stability only (its gradient
    # cancels exactly), so it is stopped — pmax has no VJP rule anyway
    m_local = lax.stop_gradient(logits_local.max(axis=-1))
    if plan.present("model"):
        v_local = logits_local.shape[-1]
        off = lax.axis_index("model") * v_local
        m = lax.pmax(m_local, "model")
        sumexp = jnp.exp(logits_local - m[..., None]).sum(axis=-1)
        sumexp = _complete_psum(sumexp, "model")
        local = labels - off
        in_range = (local >= 0) & (local < v_local)
        picked = jnp.take_along_axis(
            logits_local, jnp.where(in_range, local, 0)[..., None],
            axis=-1)[..., 0]
        picked = _complete_psum(picked * in_range.astype(picked.dtype),
                                "model")
    else:
        m = m_local
        sumexp = jnp.exp(logits_local - m[..., None]).sum(axis=-1)
        picked = jnp.take_along_axis(logits_local, labels[..., None],
                                     axis=-1)[..., 0]
    return jnp.log(sumexp) + m - picked
