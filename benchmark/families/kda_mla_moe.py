"""The ``bailing_hybrid`` family of language models as ``Ling-3.0-flash`` has
it: of every ``layer_group_size`` layers the last with latent attention
(no low-rank query path) and the others with Kimi Delta Attention, a gated
delta rule with a decay per channel (Kimi Linear, arXiv:2510.26692); a
head-wise output gate on both; leading dense gated feed-forwards and sparse
experts after them, chosen within the best groups of experts (DeepSeek-V3's
``noaux_tc``); an untied head; no prediction module (its published loss
weight is 0).

Three things live here, for every configuration whose ``family`` is
``kda_mla_moe``:

* ``build`` — the system under test, through the program's public entry
  points: ``transformer.HybridLM`` under ``DataParallelTrainer(block, None,
  'sgd', mesh_plan=MeshPlan(data=chips), dtype=...)``.
* ``flops_per_item`` — the benchmark's own count of the arithmetic one
  trained sequence needs, from shapes alone: 2 FLOPs per multiply-add,
  training = 3 x forward; every projection, the delta rule's recurrence at
  ``3 x E x E`` multiply-adds a head and token (decay aside: the state read
  by ``k``, updated, read by ``q``), causal attention's scores and values,
  the router, the shared expert, the held experts at the rows an even router
  sends them, the head.  Recomputation is not counted.
  ``flash_attention_work`` gives the operations and bytes of a step's causal
  attention alone, ``kda_scan_work`` those of the recurrence alone
  (``layer_metrics/kda_scan_roofline.py``).
* ``reference_readings`` — the plain reference: the same model, loss,
  gradients and SGD-momentum update in float32 ``jax.numpy``/``lax`` at
  matmul precision ``highest``.  It imports nothing of ``mxnet_tpu`` and is
  handed nothing the program made: the weights come from ``make_weights``
  (this file, from the seed), which ``build`` also loads into the program.

The equations (hidden ``d``; ``H`` heads)::

    h = E[ids]
    h = h + Mixer_i(RMSNorm(h)) ;  h = h + FFN_i(RMSNorm(h))
        Mixer_i: MLA where (i + 1) % layer_group_size == 0, else KDA
        FFN_i: the gated feed-forward for i < first_k_dense_replace, sparse
        experts after
    KDA(x):  q, k, v = SiLU(conv(x W_q)), SiLU(conv(x W_k)), SiLU(conv(x W_v))
                 causal depthwise convolution, short_conv_kernel_size, no bias
             q, k = q / |q|_2, k / |k|_2 a head (eps 1e-6) ; q = q * E^-0.5
             g_t = kda_lower_bound * sigmoid(exp(A_log_h) (x W_f + dt_bias))
                 a head and channel
             beta_t = sigmoid(x W_b)                       a head
             S_t = Diag(exp(g_t)) S_{t-1}
             S_t = S_t + beta_t k_t (v_t - S_t^T k_t)^T    S (E x E), S_0 = 0
             o_t = S_t^T q_t
             out = (RMSNorm(o) * sigmoid(x W_g)[head]) W_o
                 the norm over all H x E columns; W_g: d -> H
    MLA(x):  q = x W_q -> H x (nope | rope)
             [c_kv | k_r] = x W_kva ; c_kv = RMSNorm(c_kv)
             [k_nope | v] = c_kv W_kvb -> H x (nope | v)
             q_r, k_r = RoPE(., rope_theta, pairs (2j, 2j+1)); k_r one head
             o = softmax([q_nope|q_r][k_nope|k_r]^T / sqrt(nope + rope),
                 causal) v ;  out = (o * sigmoid(x W_g)[head]) W_o
    experts(x): s = sigmoid(x W_r^T) ; z = s + b
             a group's score: the sum of its two largest z; the topk_group
             best of n_group groups are kept
             chosen = top-k of z among the experts of the kept groups
             w = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor
             out = Shared(x) + sum over the chosen e held here of
                   w_e Expert_e(x)
    loss = mean CE(RMSNorm(h_L) W_head^T, y)

KDA here is the recurrence itself, one ``lax.scan`` step a token, in
checkpointed blocks of time (16,384 states of 2 MB do not fit otherwise);
the program computes it chunk by chunk.

**The share.**  ``num_experts`` counts the experts held here and
``expert_shard`` says which (``index`` of ``of``): the router is as wide as
published (``num_experts x of``), and what the experts of the other chips
would have added is left out, here as in the program.  ``vocab_size`` is the
number of embedding and head rows held.

Departures of this reference from the modelling code, none of which changes
the mathematics: the experts are the **plain form** (every expert held runs
over every token, and the choice masks its result); experts outside the kept
groups are set to minus infinity before the top-k (the HF code sets them to
0.0, which differs only where a kept expert's ``s + b`` is negative and
fewer than k kept ones are positive: with 256 kept experts, never); the
rotary pairs are turned where they stand; attention is computed a block of
query rows at a time, KDA a group of heads at a time (heads are independent
up to the output norm), the head's losses a block of tokens at a time, and
every layer a sequence at a time, recomputed in the backward pass: so that
three steps at the cell's size fit on one chip.  ``b`` is a leaf drawn from
the seed whose gradient is exactly zero; its published update is not part of
the step.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec

import attention_work
import seeds
# what a reference of this benchmark shares with the deepseek_v3 family's:
# how a variant holds the operands and the result of every matrix product
# (HOLD: the float8 control rounds both ways), the norms and the rotary turn
from families.mla_moe import (HOLD, _gated, _leaf_norms, _norm, _product,
                              _rms_norm, rope, sized)

ATTENTION_ROWS = 128       # query rows the reference attends at a time
LOSS_ROWS = 2048           # tokens whose logits the reference holds at a time
KDA_HEAD_GROUPS = 4        # groups of heads the reference's KDA runs in turn
L2_EPS = 1e-6


# ---------------------------------------------------------------------------
# the model as plain data
# ---------------------------------------------------------------------------
def shard(cfg):
    """(index, of): which of the equal shares of the routed experts is held
    here; the router's width is ``num_experts x of``."""
    s = cfg.get("expert_shard") or {"index": 0, "of": 1}
    return int(s["index"]), int(s["of"])


def program_keys(cfg):
    """``cfg`` as ``HybridLMConfig.from_hf`` takes it, ``(config, sizes)``:
    a configuration file counts the experts held under ``num_experts``; the
    program's key is the published one, the router's width, and which share
    is held is an argument beside the sizes."""
    index, of = shard(cfg)
    return (dict(cfg, num_experts=int(cfg["num_experts"]) * of),
            {"seq_len": int(cfg["seq_len"]),
             "attention_block": int(cfg["attention_block"]),
             "kda_chunk": int(cfg["kda_chunk"]),
             "expert_shard": (index, of)})


def layer_table(cfg):
    """``(mixer, feed-forward)`` of every layer held."""
    layers = int(cfg["num_hidden_layers"])
    group = int(cfg["layer_group_size"])
    dense = min(int(cfg["first_k_dense_replace"]), layers)
    return [("latent_attention" if (i + 1) % group == 0
             else "linear_attention",
             "gated_mlp" if i < dense else "sparse_experts")
            for i in range(layers)]


def _layer_leaves(cfg, mixer, ffn):
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    if mixer == "linear_attention":
        inner = h * int(cfg["head_dim"])
        width = int(cfg["short_conv_kernel_size"])
        mix = [("kda_wq", (d, inner)), ("kda_wk", (d, inner)),
               ("kda_wv", (d, inner)), ("kda_wf", (d, inner)),
               ("kda_wb", (d, h)), ("kda_wg", (d, h)),
               ("kda_conv_q", (width, inner)), ("kda_conv_k", (width, inner)),
               ("kda_conv_v", (width, inner)), ("kda_a_log", (h,)),
               ("kda_dt_bias", (inner,)), ("kda_norm", (inner,)),
               ("kda_wo", (inner, d))]
    else:
        kvr = int(cfg["kv_lora_rank"])
        nope, rope, v = (int(cfg["qk_nope_head_dim"]),
                         int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"]))
        mix = [("wq", (d, h, nope + rope)), ("wkv_a", (d, kvr + rope)),
               ("norm_kv", (kvr,)), ("wkv_b", (kvr, h, nope + v)),
               ("w_gate", (d, h)), ("wo", (h, v, d))]
    if ffn == "sparse_experts":
        f = int(cfg["moe_intermediate_size"])
        held = int(cfg["num_experts"])
        wide = held * shard(cfg)[1]
        fs = int(cfg["num_shared_experts"]) * f
        feed = [("router", (wide, d)), ("router_bias", (wide,)),
                ("moe_in", (held, d, 2 * f)), ("moe_out", (held, f, d)),
                ("shared_in", (d, 2 * fs)), ("shared_out", (fs, d))]
    else:
        f = int(cfg["intermediate_size"])
        feed = [("mlp_in", (d, 2 * f)), ("mlp_out", (f, d))]
    return [("norm1", (d,))] + mix + [("norm2", (d,))] + feed


def leaves(config, size):
    """[(name, kind, shape)] of every trained leaf, in the order
    ``HybridProgram.param_names`` lists them."""
    cfg = sized(config, size)
    d, rows = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    out = [("embed", "embed", (rows, d))]
    for i, (mixer, ffn) in enumerate(layer_table(cfg)):
        out += [("l%d_%s" % (i, kind), kind, shape)
                for kind, shape in _layer_leaves(cfg, mixer, ffn)]
    out.append(("norm_f", "norm_f", (d,)))
    if not cfg["tie_word_embeddings"]:
        out.append(("head", "head", (rows, d)))
    return out


def parameters(config, size):
    """How many parameters the cell trains."""
    return sum(math.prod(shape) for _, _, shape in leaves(config, size))


def _draw(key, kind, shape, width):
    """One leaf from ``key`` (the configuration's ``assumed``
    ``initialisation``): projections normal over the root of their fan-in,
    embedding and head normal times 0.02, norms one, the router's choosing
    bias uniform within 0.1, the short convolutions uniform within one over
    the root of their width; ``A_log`` uniform within ln 2 of nought and
    ``dt_bias`` uniform in [-8, 2], so that the gate spreads over
    (kda_lower_bound, 0): memories from a fifth of a token to a thousand."""
    f32 = jnp.float32
    if kind.startswith("norm") or kind == "kda_norm":
        return jnp.ones(shape, f32)
    if kind in ("embed", "head"):
        return jax.random.normal(key, shape, f32) * 0.02
    if kind == "router_bias":
        return jax.random.uniform(key, shape, f32, -0.1, 0.1)
    if kind == "kda_a_log":
        return jax.random.uniform(key, shape, f32, -math.log(2.0),
                                  math.log(2.0))
    if kind == "kda_dt_bias":
        return jax.random.uniform(key, shape, f32, -8.0, 2.0)
    if kind.startswith("kda_conv_"):
        return jax.random.uniform(key, shape, f32, -width ** -0.5,
                                  width ** -0.5)
    fan_in = {"wo": shape[0] * shape[1], "router": shape[-1],
              "moe_in": shape[1], "moe_out": shape[1]}.get(kind, shape[0])
    return jax.random.normal(key, shape, f32) / math.sqrt(fan_in)


def _drawn(config, size, key):
    """name -> leaf, every leaf of the seed's weights (traced)."""
    width = int(sized(config, size)["short_conv_kernel_size"])
    return {name: _draw(jax.random.fold_in(key, i), kind, shape, width)
            for i, (name, kind, shape) in enumerate(leaves(config, size))}


def make_weights(config, size, seed, sharding=None):
    """name -> float32 array, every leaf from ``seed`` in ONE jitted call
    (on ``sharding`` where given, else the default device)."""
    draw = jax.jit(functools.partial(_drawn, config, size),
                   out_shardings=sharding)
    return draw(seeds.key(seed, stream=0))


def _state_norms_fn(config, size):
    """The jitted ``(params, momentum, key) -> (first-gradient norms, update
    norms, momentum norms)`` by leaf: the gradient the optimizer got in the
    step that made ``momentum`` from nought (``m1 = -lr (g + wd w0)``), the
    change of every leaf from the seed's weights, and the momentum itself.
    The seed's weights are drawn again inside the program, so that no
    second copy of them is kept."""
    opt = config["optimizer"]
    lr, wd = float(opt["learning_rate"]), float(opt["wd"])

    @jax.jit
    def norms(params, momentum, key):
        initial = _drawn(config, size, key)
        return ({k: _norm(-momentum[k] / lr - wd * initial[k])
                 for k in params},
                {k: _norm(params[k] - initial[k]) for k in params},
                {k: _norm(momentum[k]) for k in params})

    return norms


# ---------------------------------------------------------------------------
# the benchmark's own count of the arithmetic
# ---------------------------------------------------------------------------
def forward_macs_per_token(config, size):
    """Multiply-adds of one token's forward pass at the cell's sequence
    length, from shapes alone.  Norms, convolutions, rotary turns, gates,
    the softmax, the group step, the sort and the loss are not counted."""
    cfg = sized(config, size)
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    e = int(cfg["head_dim"])
    kvr = int(cfg["kv_lora_rank"])
    nope, rope, v = (int(cfg["qk_nope_head_dim"]),
                     int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"]))
    t, rows = int(cfg["seq_len"]), int(cfg["vocab_size"])
    f, held = int(cfg["moe_intermediate_size"]), int(cfg["num_experts"])
    wide = held * shard(cfg)[1]
    mixer = {
        # q, k, v, the decay and the output; beta and the gate; the state
        # read by k, updated and read by q
        "linear_attention": 5 * d * h * e + 2 * d * h + 3 * h * e * e,
        "latent_attention": (
            d * h * (nope + rope) + d * (kvr + rope) + kvr * h * (nope + v)
            + d * h + h * v * d
            + h * (nope + rope + v) * (t + 1) / 2)}     # scores and values
    feed = {"gated_mlp": 3 * d * int(cfg["intermediate_size"]),
            "sparse_experts": (
                d * wide + 3 * d * int(cfg["num_shared_experts"]) * f
                + int(cfg["num_experts_per_tok"]) * held / wide * 3 * d * f)}
    return sum(mixer[m] + feed[ffn] for m, ffn in layer_table(cfg)) + d * rows


def flash_attention_work(config, size):
    """``((forward FLOPs, bytes), (backward FLOPs, bytes))`` that the causal
    attention of one step on one chip needs, from shapes alone
    (``attention_work.py``): the latent-attention layers held; queries and
    keys ``nope + rope`` wide, a key head a query head, values
    ``v_head_dim``, the cell's type."""
    cfg = sized(config, size)
    h = int(cfg["num_attention_heads"])
    layers = sum(m == "latent_attention" for m, _ in layer_table(cfg))
    return attention_work.causal_attention_work(
        layers, int(cfg["batch_per_chip"]), int(cfg["seq_len"]), h, h,
        int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"]),
        int(cfg["v_head_dim"]), jnp.dtype(cfg["dtype"]).itemsize)


def kda_scan_work(config, size):
    """``((forward FLOPs, bytes), (backward FLOPs, bytes))`` that the delta
    rule's recurrence of one step on one chip needs, from shapes alone, over
    the linear-attention layers held.  Operations: ``3 E^2`` multiply-adds a
    head and token forward (the state read by ``k``, updated by the outer
    product, read by ``q``), twice that backward.  Bytes, every operand and
    result once: forward ``q``, ``k``, ``v`` read in the cell's type, the
    gate (float32, a channel) and ``beta`` (float32, a head) read, ``o``
    written; backward the five read again with ``dO``, and the five
    gradients written.  Any chunked form does more of both (the ``L x L``
    system, the decayed copies, the states handed from chunk to chunk), so
    a share over 100% can only mean a wrong time."""
    cfg = sized(config, size)
    h, e = int(cfg["num_attention_heads"]), int(cfg["head_dim"])
    layers = sum(m == "linear_attention" for m, _ in layer_table(cfg))
    rows = layers * int(cfg["batch_per_chip"]) * int(cfg["seq_len"]) * h
    item = jnp.dtype(cfg["dtype"]).itemsize
    qkv, gate, beta, out = 3 * e * item, 4 * e, 4, e * item
    forward = (2 * 3 * e * e, qkv + gate + beta + out)
    backward = (2 * forward[0], 2 * (qkv + gate + beta) + out)
    return tuple((rows * flops, rows * moved)
                 for flops, moved in (forward, backward))


def flops_per_item(config, size):
    """FLOPs one trained sequence needs: 2 per multiply-add, the backward
    pass twice the forward's, ``seq_len`` tokens."""
    cfg = sized(config, size)
    return 3 * 2 * forward_macs_per_token(config, size) * int(cfg["seq_len"])


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def attention(cfg, lp, x, hold):
    """Latent attention over the normed stream ``x`` (b, t, d): no low-rank
    query path, a head-wise output gate."""
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    nope, kvr = int(cfg["qk_nope_head_dim"]), int(cfg["kv_lora_rank"])
    b, t, _ = x.shape
    q = _product("btd,dhe->bthe", x, lp["wq"], hold)
    c_kv = _product("btd,dr->btr", x, lp["wkv_a"], hold)
    k_r = rope(c_kv[..., None, kvr:], theta)
    kv = _product("btr,rhe->bthe", _rms_norm(c_kv[..., :kvr], lp["norm_kv"],
                                             eps), lp["wkv_b"], hold)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta)], axis=-1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_r, kv.shape[:3] + k_r.shape[-1:])],
                        axis=-1)
    v = kv[..., nope:]
    scale = q.shape[-1] ** -0.5
    rows = min(ATTENTION_ROWS, t)
    if t % rows:
        raise ValueError("seq_len %d is no multiple of %d" % (t, rows))

    @jax.checkpoint
    def attend(args):
        q_rows, first = args
        scores = _product("bqhe,bshe->bhqs", q_rows, k, hold) * scale
        seen = (first + jnp.arange(rows))[:, None] >= jnp.arange(t)[None, :]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return _product("bhqs,bshe->bqhe", probs, v, hold)

    q_blocks = jnp.moveaxis(q.reshape(b, t // rows, rows, *q.shape[2:]), 1, 0)
    out = lax.map(attend, (q_blocks, jnp.arange(0, t, rows)))
    out = jnp.moveaxis(out, 0, 1).reshape(b, t, *v.shape[2:])
    gate = jax.nn.sigmoid(_product("btd,dh->bth", x, lp["w_gate"], hold))
    return _product("bthe,hed->btd", out * gate[..., None], lp["wo"], hold)


def _time_block(t):
    """The largest divisor of ``t`` that is at most its square root: the
    recurrence keeps a state a block and, in the backward pass, a state a
    step of one block."""
    return max(b for b in range(1, math.isqrt(t) + 1) if t % b == 0)


def _delta_rule(q, k, v, g, beta):
    """The defining recurrence, one step a token, in checkpointed blocks of
    time.  q, k, v, g (b, t, h, e); beta (b, t, h)."""
    b, t, h, e = q.shape
    hi = lax.Precision.HIGHEST

    def step(state, inp):
        q_t, k_t, v_t, g_t, beta_t = inp
        state = jnp.exp(g_t)[..., None] * state
        seen = jnp.einsum("bhdp,bhd->bhp", state, k_t, precision=hi)
        state = state + jnp.einsum(
            "bhd,bhp->bhdp", k_t, beta_t[..., None] * (v_t - seen),
            precision=hi)
        return state, jnp.einsum("bhdp,bhd->bhp", state, q_t, precision=hi)

    @jax.checkpoint
    def block(state, inps):
        return lax.scan(step, state, inps)

    size = _time_block(t)
    seq = tuple(jnp.moveaxis(a, 1, 0).reshape((t // size, size)
                                              + a.shape[:1] + a.shape[2:])
                for a in (q, k, v, g, beta))
    _, o = lax.scan(block, jnp.zeros((b, h, e, e), q.dtype), seq)
    return jnp.moveaxis(o.reshape((t, b, h, e)), 0, 1)


def _l2_normed(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                         + L2_EPS)


def _conv(x, weight):
    """Causal depthwise convolution over time, no bias.  x (b, t, c);
    weight (K, c)."""
    width, t = weight.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(padded[:, k:k + t] * weight[k] for k in range(width))


def linear_attention(cfg, lp, x, hold):
    """Kimi Delta Attention over the normed stream ``x`` (b, t, d), a group
    of heads at a time up to the output norm."""
    h, e = int(cfg["num_attention_heads"]), int(cfg["head_dim"])
    bound = float(cfg["kda_lower_bound"])
    b, t, d = x.shape
    groups = KDA_HEAD_GROUPS if h % KDA_HEAD_GROUPS == 0 else 1
    each = h // groups

    def columns(name):
        w = lp[name]
        return jnp.moveaxis(w.reshape(w.shape[0], groups, -1), 1, 0)

    @jax.checkpoint
    def heads(part):
        wq, wk, wv, wf, wb, cq, ck, cv, a_log, dt_bias = part
        q, k, v = (hold(jax.nn.silu(_conv(
            _product("btd,de->bte", x, w, hold), c))).reshape(b, t, each, e)
            for w, c in ((wq, cq), (wk, ck), (wv, cv)))
        q = _l2_normed(q) * e ** -0.5
        k = _l2_normed(k)
        f = _product("btd,de->bte", x, wf, hold) + dt_bias
        g = bound * jax.nn.sigmoid(
            jnp.exp(a_log)[:, None] * f.reshape(b, t, each, e))
        beta = jax.nn.sigmoid(_product("btd,dh->bth", x, wb, hold))
        return _delta_rule(hold(q), hold(k), v, g, beta)

    parts = tuple(columns(n) for n in (
        "kda_wq", "kda_wk", "kda_wv", "kda_wf", "kda_wb", "kda_conv_q",
        "kda_conv_k", "kda_conv_v")) + (
        lp["kda_a_log"].reshape(groups, each),
        lp["kda_dt_bias"].reshape(groups, each * e))
    o = jnp.moveaxis(lax.map(heads, parts), 0, 2)         # (b,t,G,each,e)
    o = _rms_norm(o.reshape(b, t, h * e), lp["kda_norm"],
                  float(cfg["rms_norm_eps"]))
    gate = jax.nn.sigmoid(_product("btd,dh->bth", x, lp["kda_wg"], hold))
    o = (o.reshape(b, t, h, e) * gate[..., None]).reshape(b, t, h * e)
    return _product("bte,ed->btd", o, lp["kda_wo"], hold)


def router(cfg, lp, x, hold):
    """``(chosen, weights)`` of tokens ``x`` (T, d): (T, k) each, the choice
    limited to the experts of each token's best groups."""
    s = jax.nn.sigmoid(_product("td,ed->te", x, lp["router"], hold))
    z = s + lp["router_bias"]
    groups, kept = int(cfg["n_group"]), int(cfg["topk_group"])
    if groups > 1:
        grouped = z.reshape(z.shape[0], groups, -1)
        score = jnp.sum(lax.top_k(grouped, min(2, grouped.shape[-1]))[0],
                        axis=-1)
        # a group is kept where fewer than `kept` groups score higher (ties
        # go to the lower index, as top_k breaks them)
        order = jnp.argsort(-score, axis=-1, stable=True)
        rank = jnp.argsort(order, axis=-1, stable=True)
        z = jnp.where((rank < kept)[:, :, None], grouped,
                      -jnp.inf).reshape(z.shape)
    _, chosen = lax.top_k(z, int(cfg["num_experts_per_tok"]))
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * float(cfg["routed_scaling_factor"])


def routed_part(cfg, lp, x, hold):
    """What the experts held here add for tokens ``x`` (T, d), in the plain
    form: every one of them over every token, its result weighted by the
    token's choice of it (nought where it was not chosen)."""
    held = int(cfg["num_experts"])
    first = shard(cfg)[0] * held
    chosen, w = router(cfg, lp, x, hold)

    @jax.checkpoint
    def one(x, expert):
        number, w_in, w_out = expert
        weight = jnp.sum(jnp.where(chosen == first + number, w, 0.0),
                         axis=-1, keepdims=True)
        return weight * _gated(x, w_in, w_out, hold)

    def add(total, expert):
        return total + one(x, expert), None

    return lax.scan(add, jnp.zeros_like(x),
                    (jnp.arange(held), lp["moe_in"], lp["moe_out"]))[0]


def experts(cfg, lp, x, hold):
    """The sparse-expert feed-forward over the normed stream ``x``."""
    tokens = x.reshape(-1, x.shape[-1])
    out = (_gated(tokens, lp["shared_in"], lp["shared_out"], hold)
           + routed_part(cfg, lp, tokens, hold))
    return out.reshape(x.shape)


def _layer(cfg, mixer, ffn, hold, lp, h):
    eps = float(cfg["rms_norm_eps"])
    mix = linear_attention if mixer == "linear_attention" else attention
    h = h + mix(cfg, lp, _rms_norm(h, lp["norm1"], eps), hold)
    m = _rms_norm(h, lp["norm2"], eps)
    if ffn == "sparse_experts":
        return h + experts(cfg, lp, m, hold)
    tokens = m.reshape(-1, m.shape[-1])
    return h + _gated(tokens, lp["mlp_in"], lp["mlp_out"],
                      hold).reshape(h.shape)


def _block(cfg, params, prefix, mixer, ffn, hold, h):
    """One layer over the stream ``h`` (b, t, d), a sequence at a time
    (sequences are independent), each recomputed in the backward pass."""
    lp = {k[len(prefix):]: v for k, v in params.items()
          if k.startswith(prefix)}
    layer = jax.checkpoint(functools.partial(_layer, cfg, mixer, ffn, hold))
    return lax.map(lambda row: layer(lp, row[None])[0], h)


def token_losses(cfg, h, norm, table, y, hold):
    """Cross-entropy of every position of ``h`` (b, t, d) against ``y`` over
    the rows of ``table``, through the final norm ``norm``, float32 (b, t);
    ``LOSS_ROWS`` tokens at a time, recomputed in the backward pass."""
    @jax.checkpoint
    def losses(rows):
        h, y = rows
        logits = _product("td,vd->tv",
                          _rms_norm(h, norm, float(cfg["rms_norm_eps"])),
                          table, hold)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]

    tokens = y.size
    rows = LOSS_ROWS if tokens % LOSS_ROWS == 0 else tokens
    return lax.map(losses, (h.reshape(tokens // rows, rows, -1),
                            y.reshape(tokens // rows, rows))
                   ).reshape(y.shape)


def loss_fn(cfg, params, x, y, hold):
    """The mean cross-entropy over every position."""
    table = params["embed" if cfg["tie_word_embeddings"] else "head"]
    h = params["embed"][x]
    for i, (mixer, ffn) in enumerate(layer_table(cfg)):
        h = _block(cfg, params, "l%d_" % i, mixer, ffn, hold, h)
    return jnp.mean(token_losses(cfg, h, params["norm_f"], table, y, hold))


@functools.lru_cache(maxsize=None)
def _reference_step_fn(cfg_json, variant, update):
    """The jitted reference step ``(params, momentum, x, y) -> (loss,
    gradient norms[, params, momentum])``.  With ``update`` the state is
    donated and its successor returned (float32 weights, gradients and
    momentum of the whole model are 9.9 GB at the cell's size); without, the
    state stays as it was.  ``variant`` as ``HOLD``: ``"float32"`` is the
    reference, ``"fp8"`` the control."""
    cfg = json.loads(cfg_json)
    opt = cfg["optimizer"]
    lr, wd, mu = (float(opt["learning_rate"]), float(opt["wd"]),
                  float(opt["momentum"]))
    hold = HOLD[variant]

    def step(params, momentum, x, y):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, x, y, hold))(params)
        norms = _leaf_norms(grads)
        if not update:
            return loss, norms
        new_mom = {k: mu * momentum[k] - lr * (grads[k] + wd * params[k])
                   for k in params}
        return loss, norms, {k: params[k] + new_mom[k] for k in params}, \
            new_mom

    return jax.jit(step, donate_argnums=(0, 1) if update else ())


def reference_readings(config, size, seed, batches, variant="float32",
                       fault=None):
    """Drive the reference through ``len(batches)`` steps from the seed's
    weights and return the readings ``correctness.compare`` takes.  Under
    ``stats_norms`` it hands in the norm of the change of every leaf's
    momentum over those steps: the state the step changes that no gradient
    reaches.

    ``fault`` plants one of the faults a training cell can have:
    ``"half_batch"`` leaves out the second half of every batch (of the rows,
    or of the tokens where the batch is one row) and takes the mean over
    the rest; ``"state_unchanged"`` returns the state it was given."""
    cfg_json = json.dumps(sized(config, size), sort_keys=True)
    key = seeds.key(seed, stream=0)
    # the state goes where the batches are, replicated over their mesh, as
    # the step hands it back: the step is compiled once, not twice
    where = getattr(batches[0][0], "sharding", None)
    if isinstance(where, NamedSharding):
        where = NamedSharding(where.mesh, PartitionSpec())
    else:
        where = None
    params = make_weights(config, size, seed, where)
    momentum = jax.tree_util.tree_map(jnp.zeros_like, params)
    if where is not None:
        momentum = jax.device_put(momentum, where)
    step = _reference_step_fn(cfg_json, variant, fault != "state_unchanged")
    losses, grad_norms = [], None
    with jax.default_matmul_precision("highest"):
        for x, y in batches:
            if fault == "half_batch":
                if x.shape[0] > 1:
                    x, y = x[:x.shape[0] // 2], y[:x.shape[0] // 2]
                else:
                    x, y = x[:, :x.shape[1] // 2], y[:, :x.shape[1] // 2]
            loss, norms, *state = step(params, momentum, x, y)
            if state:
                params, momentum = state
            losses.append(float(loss))
            if grad_norms is None:
                grad_norms = jax.device_get(norms)
    _, update, moved = jax.device_get(
        _state_norms_fn(config, size)(params, momentum, key))
    return {"losses": losses,
            "grad_norms": {k: float(v) for k, v in grad_norms.items()},
            "update_norms": {k: float(v) for k, v in update.items()},
            "stats_norms": {k: float(v) for k, v in moved.items()}}


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------
class Program:
    """``HybridLM`` under ``DataParallelTrainer``'s mesh tier: what the
    window drives.  ``step``/``flush`` are the trainer's own; ``snapshot``
    reduces the training state to norms by leaf on the device (the float32
    weights and their momentum are 6.6 GB at the cell's size)."""

    def __init__(self, config, size, mesh, seed):
        import mxnet_tpu as mx
        from mxnet_tpu.parallel import DataParallelTrainer, MeshPlan
        from mxnet_tpu.transformer import HybridLM, HybridLMConfig

        mx.random.seed(int(seed) & 0x7FFFFFFF)
        cfg = sized(config, size)
        self._key = seeds.key(seed, stream=0)
        self._norms = _state_norms_fn(config, size)
        keys, sizes = program_keys(cfg)
        model = HybridLMConfig.from_hf(keys, **sizes)
        # the seed's weights go in the way a checkpoint's would
        weights = make_weights(
            config, size, seed, NamedSharding(mesh, PartitionSpec()))
        plan = MeshPlan(data=mesh.devices.size)
        block = HybridLM(model, params=weights)
        if block.mesh_program(plan).param_names != [
                n for n, _, _ in leaves(config, size)]:
            raise RuntimeError("the program's leaves are not the reference's")
        opt = dict(config["optimizer"])
        self.trainer = DataParallelTrainer(
            block, None, opt.pop("name"), opt, mesh_plan=plan,
            dtype=config["dtype"])

    def step(self, data, label):
        """One training step; the loss as a lazy device scalar."""
        return self.trainer.step(data, label)._data

    def flush(self):
        self.trainer.flush()

    def snapshot(self):
        """(first-gradient norms, update norms, momentum norms) by leaf, as
        ``_state_norms_fn`` reads them off the trainer's state."""
        self.flush()
        params, states = self.trainer.device_arrays()
        if len(states) != len(params):
            raise RuntimeError("one momentum leaf a parameter is expected, "
                               "got %d for %d" % (len(states), len(params)))
        momentum = dict(zip(params, states))
        return [{k: float(v) for k, v in part.items()} for part in
                jax.device_get(self._norms(params, momentum, self._key))]

    def readings(self, losses, after_first, after_last):
        """The program's side of the comparison: the first gradient as the
        optimizer got it follows from the momentum after one step."""
        return {"losses": [float(v) for v in losses],
                "grad_norms": after_first[0], "update_norms": after_last[1],
                "stats_norms": after_last[2]}

    def close(self):
        """Drop the training state so that the reference has the chip."""
        self.flush()
        self.trainer = None


def build(config, size, mesh, seed):
    return Program(config, size, mesh, seed)
