"""Latent attention (the ``deepseek_v3`` family's MLA) as a mixer of
:class:`~.hybrid.HybridLM`'s layer table (docs/transformer.md "The layer
table").

Queries and key-values go through low-rank paths, and the positions are
rotary, carried by a decoupled part of every query head and by one key of
``qk_rope_dim`` columns that all heads share::

    c_q = RMSNorm(x W_qa)                    q = c_q W_qb -> heads x (nope|rope)
    [c_kv | k_r] = x W_kva                   c_kv = RMSNorm(c_kv)
    [k_nope | v] = c_kv W_kvb -> heads x (nope | v)
    q_r, k_r = RoPE(.)                       k_r one head, broadcast to all
    o = softmax([q_nope|q_r] [k_nope|k_r]^T / sqrt(nope + rope), causal) v
    out = o W_o

Two variants, by the configuration (``bailing_hybrid``'s): with
``q_lora_rank`` None the query path is one product and no norm, ``q = x W_q``;
with ``attention_gate`` the result is gated head by head before the output
product, ``out = (o * sigmoid(x W_g)[head]) W_o``, one scalar a head and
token (``W_g``: d -> heads).

The scores and the output product are ``hybrid.py``'s, through
:func:`~.hybrid.causal_gqa_attention` with as many key-value heads as heads:
``nope + rope`` query-key columns against ``v_head_dim`` value columns, as
the flash kernels of ``ops/pallas_kernels.py`` where ``flash_tiles`` takes
the shapes (two widths are theirs to take) and as blocks of rows elsewhere.
In training nothing is cached, so the key-values are expanded from the
latent; a latent cache is serving's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from .layers import rms_norm
from .ssm import PROJECTION

__all__ = ["rope_interleaved", "queries_keys_values", "gated", "leaves",
           "product_widths"]


def leaves(cfg):
    """[(kind, shape)] of the mixer's leaves, in declaration order."""
    d, h = cfg.d_model, cfg.n_heads
    nope, rope, v = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    if cfg.q_lora_rank is None:
        queries = [("wq", (d, h, nope + rope))]
    else:
        queries = [("wq_a", (d, cfg.q_lora_rank)),
                   ("norm_q", (cfg.q_lora_rank,)),
                   ("wq_b", (cfg.q_lora_rank, h, nope + rope))]
    return queries + [("wkv_a", (d, cfg.kv_lora_rank + rope)),
                      ("norm_kv", (cfg.kv_lora_rank,)),
                      ("wkv_b", (cfg.kv_lora_rank, h, nope + v))] \
        + ([("w_gate", (d, h))] if cfg.attention_gate else []) \
        + [("wo", (h, v, d))]


def product_widths(cfg):
    """Widths of the mixer's tagged projection products."""
    h = cfg.n_heads
    return ([] if cfg.q_lora_rank is None else [cfg.q_lora_rank]) + [
            h * (cfg.qk_nope_dim + cfg.qk_rope_dim),
            cfg.kv_lora_rank + cfg.qk_rope_dim,
            h * (cfg.qk_nope_dim + cfg.v_head_dim), cfg.d_model]


def rope_interleaved(x, theta, offset=0, axis=1):
    """Rotary positions over ``x`` (b, t, ..., r; the positions along
    ``axis``) in float32: the pair of columns ``(2j, 2j+1)`` of position
    ``p`` turns by ``p theta^(-2j/r)`` (``rope_interleave``: the pairs are
    neighbours, not the two halves).  The turned pair stays where it was, so
    the scores are those of the HF code, which moves the pairs apart first
    in queries and keys alike."""
    r = x.shape[-1]
    f32 = jnp.float32
    axis %= x.ndim
    turns = theta ** (-jnp.arange(0, r, 2, dtype=f32) / r)
    angle = (offset + jnp.arange(x.shape[axis], dtype=f32))[:, None] * turns
    shape = tuple(n if i in (axis, x.ndim - 1) else 1
                  for i, n in enumerate(x.shape))
    cos = jnp.repeat(jnp.cos(angle), 2, axis=-1).reshape(shape)
    sin = jnp.repeat(jnp.sin(angle), 2, axis=-1).reshape(shape)
    # the pair's other column, signed: (-x[2j+1], x[2j]), as a product with
    # a signed permutation (exact: one term of +-1 a column).  As two rolls
    # along the columns it is a shuffle of lanes where the columns are the
    # minor axis, which is where the flash kernels want them: 6.3 ms a layer
    # forward at the JoyAI cell against 2.7 in the layout XLA chose before
    columns = np.arange(r)
    swap = np.zeros((r, r), np.float32)
    swap[columns + 1 - 2 * (columns % 2), columns] = 2 * (columns % 2) - 1
    other = jnp.einsum("...r,rs->...s", x, jnp.asarray(swap, x.dtype),
                       preferred_element_type=f32,
                       precision=jax.lax.Precision.HIGHEST)
    return (x.astype(f32) * cos + other * sin).astype(x.dtype)


def queries_keys_values(lp, x, cfg):
    """``(q, k, v)`` of the mixer over its leaves ``lp`` (kind -> array)
    and the normed residual stream ``x`` (b, t, d): q, k (b, t, heads,
    nope + rope) with the rotary part turned, v (b, t, heads, v_head_dim).
    The low-rank products carry the tag of a projection product the
    backward pass may keep.  The heads' products are made heads before time
    (``bhte``), the layout the flash kernels read, and turned and joined so:
    what is returned are views, and ``causal_gqa_attention``'s transposes
    undo them (made time before heads, each of q, k and their gradients
    cost a transposing copy of 200 MB a layer at the JoyAI cell: PERF.md
    section 6, PR 36)."""
    nope, rank = cfg.qk_nope_dim, cfg.kv_lora_rank
    with jax.named_scope("mla_q_proj"):
        if cfg.q_lora_rank is None:
            q = checkpoint_name(jnp.einsum("btd,dhe->bhte", x, lp["wq"]),
                                PROJECTION)
        else:
            c_q = checkpoint_name(x @ lp["wq_a"], PROJECTION)
            c_q = rms_norm(c_q, lp["norm_q"], cfg.norm_eps)
            q = checkpoint_name(
                jnp.einsum("btr,rhe->bhte", c_q, lp["wq_b"]), PROJECTION)
    with jax.named_scope("mla_kv_proj"):
        c_kv = checkpoint_name(x @ lp["wkv_a"], PROJECTION)
        k_r = c_kv[..., rank:]
        c_kv = rms_norm(c_kv[..., :rank], lp["norm_kv"], cfg.norm_eps)
        kv = checkpoint_name(jnp.einsum("btr,rhe->bhte", c_kv, lp["wkv_b"]),
                             PROJECTION)
    with jax.named_scope("mla_rope"):
        q_r = rope_interleaved(q[..., nope:], cfg.rope_theta, axis=2)
        k_r = rope_interleaved(k_r[:, None], cfg.rope_theta, axis=2)
        q = jnp.concatenate([q[..., :nope], q_r], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_r, kv.shape[:3] + k_r.shape[-1:])], axis=-1)
    return tuple(a.transpose(0, 2, 1, 3) for a in (q, k, kv[..., nope:]))


def gated(lp, x, o):
    """The head-wise output gate: ``o`` (b, t, heads, v) times ``sigmoid(x
    W_g)``, one scalar a head and token, the sigmoid in float32."""
    gate = jax.nn.sigmoid((x @ lp["w_gate"]).astype(jnp.float32))
    return o * gate[..., None].astype(o.dtype)
