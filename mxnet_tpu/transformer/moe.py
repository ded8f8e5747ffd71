"""A sparse-expert feed-forward (the ``deepseek_v3`` family's) that is told
which of the routed experts it holds, as a feed-forward of
:class:`~.hybrid.HybridLM`'s layer table (docs/transformer.md "The layer
table")::

    s = sigmoid(x W_r^T)                     float32, over all the experts
    chosen = top-k of (s + b)                b chooses and never weighs
        with ``n_group`` > 1 (group-limited choice, ``noaux_tc``): a group's
        score is the sum of its two largest ``s + b``; only the experts of
        the ``topk_group`` best groups can be chosen
    w = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling
    out = Shared(x) + sum over the chosen e held here of w_e Expert_e(x)

The layer routes over every published expert and computes the part of the
result its own experts give: experts ``index * held .. (index + 1) * held``
of ``cfg.expert_shard = (index, of)``.  What the absent experts would have
added is left out; on one chip the layer runs without an exchange.

**No token is dropped.**  The (token, chosen expert held here) pairs are
sorted by expert into a buffer of a static number of rows
(:func:`buffer_rows`: :data:`BUFFER_FACTOR` times the rows an even router
sends here), and ``lax.ragged_dot`` runs every expert over its own rows, so
the products' work follows the rows routed here and not tokens x experts
held; the buffer's rows past the last routed one are of no expert and are
masked.  Where more rows are routed here than the buffer has, a ``lax.cond``
takes the same computation a chunk of the tokens at a time, each chunk small
enough that its worst case (every token to ``min(k, held)`` experts held)
fits the buffer, and gives the same answer in the buffer's memory.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from .layers import gated_mlp
from .ssm import PROJECTION

__all__ = ["BUFFER_FACTOR", "leaves", "router_choice", "held_loads",
           "buffer_rows", "worst_rows", "expected_rows", "sparse_experts"]

# the buffer of routed rows over the rows an even router sends here.  Sized
# to routing by seed-drawn choosing biases within 0.1, which send one to two
# and a half times the even rows a layer here (PERF.md, PR 35; a quarter to
# two and a half times with the group step, PR 38); a trained, balanced
# router would need less
BUFFER_FACTOR = 3


def leaves(cfg):
    """[(kind, shape)] of the feed-forward's leaves, in declaration order:
    the router over all the experts and its choosing bias, the experts held
    here stacked, the shared expert."""
    d, f, held = cfg.d_model, cfg.moe_d_ff, cfg.experts_held
    shared = cfg.n_shared_experts * f
    return [("router", (cfg.n_routed_experts, d)),
            ("router_bias", (cfg.n_routed_experts,)),
            ("moe_in", (held, d, 2 * f)), ("moe_out", (held, f, d)),
            ("shared_in", (d, 2 * shared)), ("shared_out", (shared, d))]


def expected_rows(cfg, tokens):
    """Rows an even router sends the experts held here."""
    return (tokens * cfg.experts_per_token * cfg.experts_held
            / cfg.n_routed_experts)


def worst_rows(cfg, tokens):
    """The most rows any routing sends here: a token's choices are
    distinct."""
    return tokens * min(cfg.experts_per_token, cfg.experts_held)


def buffer_rows(cfg, tokens):
    """The static size of the buffer of routed rows: the expected rows
    times :data:`BUFFER_FACTOR`, a multiple of 8, at most the worst case."""
    rows = -(-int(BUFFER_FACTOR * expected_rows(cfg, tokens)) // 8)
    return min(8 * max(rows, 1), worst_rows(cfg, tokens))


def router_choice(x, router, bias, cfg):
    """``(chosen, weights)`` of tokens ``x`` (T, d): the ``experts_per_token``
    experts with the largest ``s + b`` of each token (T, k), among the
    experts of the token's ``topk_group`` best groups where the
    configuration has groups, and their weights from ``s`` alone,
    float32."""
    s = jax.nn.sigmoid(jnp.einsum("td,ed->te", x, router,
                                  preferred_element_type=jnp.float32))
    z = s + bias.astype(jnp.float32)
    if cfg.n_group > 1:
        with jax.named_scope("moe_group_choice"):
            z = _kept_groups(z, cfg.n_group, cfg.topk_group)
    _, chosen = lax.top_k(z, cfg.experts_per_token)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * cfg.routed_scaling


def _kept_groups(z, groups, kept):
    """``z`` (T, experts) with the experts outside each token's ``kept``
    best of ``groups`` equal groups at ``-inf``; a group's score is the sum
    of its two largest entries (DeepSeek-V3's group-limited choice)."""
    grouped = z.reshape(z.shape[0], groups, -1)
    score = jnp.sum(lax.top_k(grouped, min(2, grouped.shape[-1]))[0],
                    axis=-1)
    _, best = lax.top_k(score, kept)                      # (T, kept)
    keep = jnp.any(best[:, :, None] == jnp.arange(groups)[None, None, :],
                   axis=1)                                # (T, groups)
    return jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(z.shape)


def _held(chosen, cfg):
    """Each choice as the number of its expert among the ones held here,
    ``experts_held`` where the expert is another chip's: (T * k,)."""
    held = cfg.experts_held
    local = chosen.reshape(-1) - cfg.expert_shard[0] * held
    return jnp.where((local >= 0) & (local < held), local, held)


def _loads(local, held):
    """Rows routed to each expert held here: (held,) int32."""
    return jnp.sum(local[:, None] == jnp.arange(held)[None, :], axis=0,
                   dtype=jnp.int32)


def held_loads(lp, x, cfg):
    """Rows the router sends each expert held here for ``x`` (b, t, d)."""
    chosen, _ = router_choice(x.reshape(-1, x.shape[-1]), lp["router"],
                              lp["router_bias"], cfg)
    return _loads(_held(chosen, cfg), cfg.experts_held)


def _routed(lp, x, local, sizes, w, rows):
    """The held experts' part of the result for tokens ``x`` (T, d), float32
    (T, d), through a buffer of ``rows`` rows, which has to hold
    ``sum(sizes)``."""
    k = w.shape[1]
    with jax.named_scope("moe_dispatch"):
        # the choices held here come first, expert by expert
        order = jnp.argsort(local, stable=True)[:rows]
        token = order // k
        live = jnp.arange(rows) < jnp.sum(sizes)
        weight = jnp.where(live, w.reshape(-1)[order], 0.0)
        # the buffer's rows past the last routed one are of no expert: they
        # are nought on the way in, after each product and on the way out,
        # in values and gradients (a grouped product leaves the rows of no
        # group as it finds them, and what is found there need not be a
        # number: PERF.md, PR 35)
        rows_in = jnp.where(live[:, None], jnp.take(x, token, axis=0), 0)
    with jax.named_scope("moe_experts"):
        hidden = jnp.where(live[:, None],
                           lax.ragged_dot(rows_in, lp["moe_in"], sizes), 0)
        a, b = jnp.split(checkpoint_name(hidden, PROJECTION), 2, axis=-1)
        rows_out = lax.ragged_dot(jax.nn.silu(a) * b, lp["moe_out"], sizes)
    with jax.named_scope("moe_combine"):
        rows_out = jnp.where(live[:, None], rows_out.astype(jnp.float32)
                             * weight[:, None], 0.0)
        return jnp.zeros(x.shape, jnp.float32).at[token].add(rows_out)


def _routed_in_chunks(lp, x, local, sizes, w, chunks):
    """:func:`_routed` over ``chunks`` equal chunks of the tokens in turn,
    each through a buffer of its own worst case: whatever the routing, the
    rows fit.  ``sizes`` is not read: every chunk counts its own."""
    del sizes
    tokens, k = w.shape
    held = lp["moe_in"].shape[0]
    each = tokens // chunks

    @jax.checkpoint
    def one(chunk):
        x, local, w = chunk
        return _routed(lp, x, local, _loads(local, held), w,
                       rows=each * min(k, held))

    return lax.map(one, (x.reshape(chunks, each, -1),
                         local.reshape(chunks, each * k),
                         w.reshape(chunks, each, k))).reshape(x.shape)


def sparse_experts(lp, x, cfg):
    """The feed-forward over its leaves ``lp`` (kind -> array) and the
    normed residual stream ``x`` (b, t, d)."""
    tokens = x.reshape(-1, x.shape[-1])
    with jax.named_scope("moe_router"):
        chosen, w = router_choice(tokens, lp["router"], lp["router_bias"],
                                  cfg)
        local = _held(chosen, cfg)
        sizes = _loads(local, cfg.experts_held)
    rows = buffer_rows(cfg, tokens.shape[0])
    worst = worst_rows(cfg, tokens.shape[0])
    if rows < worst:
        # the fewest equal chunks whose worst cases fit the buffer
        chunks = next(c for c in range(-(-worst // rows), tokens.shape[0] + 1)
                      if tokens.shape[0] % c == 0)
        routed = lax.cond(
            jnp.sum(sizes) <= rows,
            lambda *args: _routed(*args, rows=rows),
            lambda *args: _routed_in_chunks(*args, chunks=chunks),
            lp, tokens, local, sizes, w)
    else:
        routed = _routed(lp, tokens, local, sizes, w, rows=rows)
    with jax.named_scope("moe_shared_expert"):
        shared = gated_mlp({"mlp_in": lp["shared_in"],
                            "mlp_out": lp["shared_out"]}, tokens)
    with jax.named_scope("moe_combine"):
        return (shared.astype(jnp.float32) + routed).astype(
            x.dtype).reshape(x.shape)
