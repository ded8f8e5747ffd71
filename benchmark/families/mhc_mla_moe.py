"""The ``xing4_0`` family of language models as ``Xing4.0-29B-A4B`` has it:
the ``deepseek_v3`` layers (latent attention with the low-rank query path
in every layer, a leading dense gated feed-forward and sparse experts after
it, an untied head) with two changes: the residual stream is ``hc_mult``
streams joined around every sublayer by manifold-constrained
hyper-connections ("mHC: Manifold-Constrained Hyper-Connections",
DeepSeek-AI, after Hyper-Connections, Zhu et al., arXiv:2409.19606), and
the rotary positions are YaRN-scaled (DeepSeek-V3's ``rope_scaling`` of
type ``yarn``).  No prediction module (``num_nextn_predict_layers`` 0: with
several streams its equations are not given).

Three things live here, for every configuration whose ``family`` is
``mhc_mla_moe``:

* ``build`` — the system under test, through the program's public entry
  points: ``transformer.HybridLM`` under ``DataParallelTrainer(block, None,
  'sgd', mesh_plan=MeshPlan(data=chips), dtype=...)``.
* ``flops_per_item`` — the benchmark's own count of the arithmetic one
  trained sequence needs, from shapes alone: 2 FLOPs per multiply-add,
  training = 3 x forward; every projection (the hyper-connections' ``phi``
  too), causal attention's scores and values, the router, the shared
  expert, the held experts at the rows an even router sends them, the head.
  Recomputation is not counted.  ``flash_attention_work`` gives the
  operations and bytes of a step's causal attention alone,
  ``hyper_connection_work`` those of the hyper-connections alone
  (``layer_metrics/hyper_conn_roofline.py``).
* ``reference_readings`` — the plain reference: the same model, loss,
  gradients and SGD-momentum update in float32 ``jax.numpy``/``lax`` at
  matmul precision ``highest``.  It imports nothing of ``mxnet_tpu`` and is
  handed nothing the program made: the weights come from ``make_weights``
  (this file, from the seed), which ``build`` also loads into the program.

The equations (hidden ``C``; ``n = hc_mult`` streams; ``h`` heads)::

    x_0 = [e; ...; e]   n copies of e = E[ids]; the stream (b, t, n, C)
    for each sublayer F of a layer (latent attention, then the dense
    feed-forward for i < first_k_dense_replace or sparse experts after):
      v      = RMSNorm(vec(x))              over the n C values, no gain
      h_pre  = a_pre (v phi_pre) + b_pre
      h_post = a_post (v phi_post) + b_post
      G_res  = a_res mat(v phi_res) + b_res
      H_pre  = sigmoid(h_pre) ;  H_post = 2 sigmoid(h_post)
      H_res  = Sinkhorn(exp(clamp(G_res, mhc_h_res_clamp_min, _max)))
               hc_sinkhorn_iters times: columns, then rows, each divided by
               its sum plus hc_eps
      u      = sum_j H_pre[j] x_j  -> RMSNorm (norm1 / norm2) -> F -> y
      x'_i   = sum_j H_res[i, j] x_j + H_post[i] y
    h_L = sum_i x_L,i ;  loss = mean CE(RMSNorm(h_L) W_head^T, y)
    MLA(x):  c_q = RMSNorm(x W_qa) ; q = c_q W_qb -> h x (nope | rope)
             [c_kv | k_r] = x W_kva ; c_kv = RMSNorm(c_kv)
             [k_nope | v] = c_kv W_kvb -> h x (nope | v)
             q_r, k_r = RoPE(., YaRN turns f'_j, pairs (2j, 2j+1)), cos and
             sin times mscale(s, mscale) / mscale(s, mscale_all_dim)
             o = softmax(scale [q_nope|q_r][k_nope|k_r]^T, causal) v
             scale = (nope + rope)^-1/2 mscale(s, mscale_all_dim)^2
             out = o W_o
    YaRN:    f_j = theta^(-2j/r) ; d(b) = r ln(L0 / (2 pi b)) / (2 ln theta)
             lo = max(floor(d(beta_fast)), 0)
             hi = min(ceil(d(beta_slow)), r - 1)
             ramp = clamp((j - lo) / (hi - lo), 0, 1)
             f'_j = f_j (1 - ramp_j) + (f_j / s) ramp_j
             mscale(s, m) = 0.1 m ln s + 1
    experts: DeepSeek-V3's sigmoid noaux_tc router, n_group 1, a shared
             expert, norm_topk_prob (``mla_moe.py``)

**The share.**  ``n_routed_experts`` counts the experts held here and
``expert_shard`` says which (``index`` of ``of``): the router is as wide as
published (``n_routed_experts x of``), and what the experts of the other
chips would have added is left out, here as in the program.  ``vocab_size``
is the number of embedding and head rows held.

Departures of this reference from the published description, none of
which changes the mathematics: the experts are the plain form (every
expert held runs over every token, and the choice masks its result); the
rotary pairs are turned where they stand; attention is computed a block of
query rows at a time, every layer a sequence at a time, recomputed in the
backward pass, so that three steps at the cell's size fit on one chip.  The
readings the keys leave open are the configuration's ``assumed`` (a)-(g):
n copies of the embedding, the streams summed at the end, a norm without a
gain, ``hc_eps`` in the sums of Sinkhorn, columns before rows, the clamp
before ``exp``, the initial ``a`` and ``b``.  ``b`` of the router is a leaf
drawn from the seed whose gradient is exactly zero; its published update is
not part of the step.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec

import seeds
# what this family shares with the deepseek_v3 family's reference: how a
# variant holds the operands and the result of every matrix product, the
# norms, the experts and the loss, the leaves of a layer without its
# connections, the count of attention's work and the `Program` class
from families import mla_moe
from families.mla_moe import (HOLD, _gated, _leaf_norms, _norm, _product,
                              _rms_norm, experts, layer_table, sized,
                              token_losses)

ATTENTION_ROWS = 128       # query rows the reference attends at a time
HC_ALPHA = 0.01            # the start of every a_pre, a_post, a_res


# ---------------------------------------------------------------------------
# the model as plain data
# ---------------------------------------------------------------------------
# ``cfg`` as ``HybridLMConfig.from_hf`` takes it: the keys and the share
# are the deepseek_v3 family's
program_keys = mla_moe.program_keys


def _connection_leaves(cfg):
    """Both sublayers' connection leaves of a layer: ``phi`` (n C, n^2 +
    2n; the columns of pre, post, res), ``bias`` (the same order) and
    ``alpha`` (a_pre, a_post, a_res)."""
    n, d = int(cfg["hc_mult"]), int(cfg["hidden_size"])
    width = n * n + 2 * n
    return [(sub + kind, shape) for sub in ("hc_mix_", "hc_ffn_")
            for kind, shape in (("phi", (n * d, width)), ("bias", (width,)),
                                ("alpha", (3,)))]


def leaves(config, size):
    """[(name, kind, shape)] of every trained leaf, in the order
    ``HybridProgram.param_names`` lists them."""
    cfg = sized(config, size)
    d, rows = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    out = [("embed", "embed", (rows, d))]
    for i, ffn in enumerate(layer_table(cfg)):
        out += [("l%d_%s" % (i, kind), kind, shape)
                for kind, shape in (mla_moe._layer_leaves(cfg, ffn)
                                    + _connection_leaves(cfg))]
    out.append(("norm_f", "norm_f", (d,)))
    if not cfg["tie_word_embeddings"]:
        out.append(("head", "head", (rows, d)))
    return out


def parameters(config, size):
    """How many parameters the cell trains."""
    return sum(math.prod(shape) for _, _, shape in leaves(config, size))


def _draw(key, kind, shape):
    """One leaf from ``key`` (the configuration's ``assumed``
    ``initialisation``): a connection's ``alpha`` ``HC_ALPHA`` and its
    ``bias`` uniform within one; the rest as ``mla_moe._draw`` (``phi`` a
    projection, normal over the root of its fan-in)."""
    if kind.startswith("hc_") and kind.endswith("_alpha"):
        return jnp.full(shape, HC_ALPHA, jnp.float32)
    if kind.startswith("hc_") and kind.endswith("_bias"):
        return jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0)
    return mla_moe._draw(key, kind, shape)


def _drawn(config, size, key):
    """name -> leaf, every leaf of the seed's weights (traced)."""
    return {name: _draw(jax.random.fold_in(key, i), kind, shape)
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
    length, from shapes alone: ``mla_moe``'s count of the layers and the
    head, and every sublayer's product with ``phi``.  Norms, rotary turns,
    the mixes, Sinkhorn, the softmax, the sort and the loss are not
    counted."""
    cfg = sized(config, size)
    n, d = int(cfg["hc_mult"]), int(cfg["hidden_size"])
    sublayers = 2 * int(cfg["num_hidden_layers"])
    return mla_moe.forward_macs_per_token(config, size) \
        + sublayers * n * d * (n * n + 2 * n)


# a step's causal attention: every layer held, ``nope + rope`` query-key
# columns, ``v_head_dim`` value columns, as the deepseek_v3 family counts it
flash_attention_work = mla_moe.flash_attention_work


def hyper_connection_work(config, size):
    """``((forward FLOPs, bytes), (backward FLOPs, bytes))`` that a step's
    hyper-connections need on one chip, from shapes alone, whatever
    implements them.  A sublayer and token, the stream ``n C`` wide:

    * bytes in the cell's type: forward ``(3n + 2) C`` (the stream read
      twice, for the coefficients and for the mixes, and written once;
      ``u`` written, ``y`` read), backward ``(6n + 3) C``;
    * operations: forward ``2 n C (n^2 + 2n)`` for the product with
      ``phi``, ``2 (n^2 + 2n) C`` for the mixes, and Sinkhorn's ``exp`` and
      ``4 n^2`` a iteration (two sums and two divisions); backward twice
      that.

    The expansion of the embedding and the merge before the final norm are
    counted once each way: ``(n + 1) C`` elements moved each.  The
    coefficients' own arrays (``(n^2 + 2n)`` float32 a token) are left
    out."""
    cfg = sized(config, size)
    n, c = int(cfg["hc_mult"]), int(cfg["hidden_size"])
    item = jnp.dtype(cfg["dtype"]).itemsize
    tokens = int(cfg["batch_per_chip"]) * int(cfg["seq_len"])
    sublayers = 2 * int(cfg["num_hidden_layers"])
    width = n * n + 2 * n
    flops = (2 * n * c * width + 2 * width * c
             + n * n * (1 + 4 * int(cfg["hc_sinkhorn_iters"])))
    ends = 2 * (n + 1) * c * item
    forward = (sublayers * flops, sublayers * (3 * n + 2) * c * item + ends)
    backward = (2 * forward[0], sublayers * (6 * n + 3) * c * item + ends)
    return tuple((tokens * ops, tokens * moved)
                 for ops, moved in (forward, backward))


def flops_per_item(config, size):
    """FLOPs one trained sequence needs: 2 per multiply-add, the backward
    pass twice the forward's, ``seq_len`` tokens."""
    cfg = sized(config, size)
    return 3 * 2 * forward_macs_per_token(config, size) * int(cfg["seq_len"])


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def _yarn_mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_turns(cfg):
    """``(turns, factor)``: YaRN's turn of every rotary pair, float32
    (r/2), and the factor of cos and sin, from the formulas."""
    scaling = cfg["rope_scaling"]
    r, theta = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"])
    s = float(scaling["factor"])
    start = float(scaling["original_max_position_embeddings"])

    def correction(rotations):
        return r * math.log(start / (2 * math.pi * rotations)) \
            / (2 * math.log(theta))

    lo = max(math.floor(correction(scaling["beta_fast"])), 0)
    hi = min(math.ceil(correction(scaling["beta_slow"])), r - 1)
    j = np.arange(r // 2, dtype=np.float64)
    ramp = np.clip((j - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    f = theta ** (-2.0 * j / r)
    turns = f * (1.0 - ramp) + f / s * ramp
    return (turns.astype(np.float32),
            _yarn_mscale(s, scaling["mscale"])
            / _yarn_mscale(s, scaling["mscale_all_dim"]))


def scores_scale(cfg):
    """``(nope + rope)^-1/2 mscale(s, mscale_all_dim)^2``."""
    scaling = cfg["rope_scaling"]
    width = int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
    return width ** -0.5 * _yarn_mscale(float(scaling["factor"]),
                                        scaling["mscale_all_dim"]) ** 2


def rope(x, turns, factor):
    """Rotary positions over ``x`` (b, t, ..., r): columns ``(2j, 2j+1)`` of
    position ``p`` turn by ``p turns[j]``, cos and sin times ``factor``."""
    r, t = x.shape[-1], x.shape[1]
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(turns)
    angle = angle.reshape((1, t) + (1,) * (x.ndim - 3) + (r // 2,))
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    first, second = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([first * cos - second * sin,
                        second * cos + first * sin], axis=-1)
    return turned.reshape(x.shape)


def attention(cfg, lp, x, hold):
    """Latent attention over the normed stream ``x`` (b, t, C), the rotary
    part YaRN's."""
    eps = float(cfg["rms_norm_eps"])
    nope, kvr = int(cfg["qk_nope_head_dim"]), int(cfg["kv_lora_rank"])
    turns, factor = yarn_turns(cfg)
    b, t, _ = x.shape
    c_q = _rms_norm(_product("btd,dr->btr", x, lp["wq_a"], hold),
                    lp["norm_q"], eps)
    q = _product("btr,rhe->bthe", c_q, lp["wq_b"], hold)
    c_kv = _product("btd,dr->btr", x, lp["wkv_a"], hold)
    k_r = rope(c_kv[..., None, kvr:], turns, factor)
    kv = _product("btr,rhe->bthe", _rms_norm(c_kv[..., :kvr], lp["norm_kv"],
                                             eps), lp["wkv_b"], hold)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], turns, factor)],
                        axis=-1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_r, kv.shape[:3] + k_r.shape[-1:])],
                        axis=-1)
    v = kv[..., nope:]
    scale = scores_scale(cfg)
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
    return _product("bthe,hed->btd", out, lp["wo"], hold)


def sinkhorn(m, iters, eps):
    """``iters`` times: every column of ``m`` (..., n, n) divided by its sum
    plus ``eps``, then every row."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
    return m


def hyper_connection(cfg, lp, sub, x, sublayer, hold):
    """The stream ``x`` (b, t, n, C) after ``sublayer`` (its own norm and
    body) in the hyper-connection whose leaves are ``lp[sub + ...]``."""
    n = int(cfg["hc_mult"])
    b, t, _, c = x.shape
    flat = x.reshape(b, t, n * c)
    v = flat * lax.rsqrt(jnp.mean(jnp.square(flat), axis=-1, keepdims=True)
                         + float(cfg["rms_norm_eps"]))
    proj = _product("btk,kc->btc", v, lp[sub + "phi"], hold)
    alpha, bias = lp[sub + "alpha"], lp[sub + "bias"]
    h_pre = alpha[0] * proj[..., :n] + bias[:n]
    h_post = alpha[1] * proj[..., n:2 * n] + bias[n:2 * n]
    g_res = (alpha[2] * proj[..., 2 * n:] + bias[2 * n:]).reshape(b, t, n, n)
    pre = jax.nn.sigmoid(h_pre)
    post = 2.0 * jax.nn.sigmoid(h_post)
    res = sinkhorn(jnp.exp(jnp.clip(g_res, float(cfg["mhc_h_res_clamp_min"]),
                                    float(cfg["mhc_h_res_clamp_max"]))),
                   int(cfg["hc_sinkhorn_iters"]), float(cfg["hc_eps"]))
    u = sum(pre[..., j, None] * x[..., j, :] for j in range(n))
    y = sublayer(u)
    return jnp.stack([sum(res[..., i, j, None] * x[..., j, :]
                          for j in range(n)) + post[..., i, None] * y
                      for i in range(n)], axis=-2)


def _layer(cfg, ffn, hold, lp, x):
    eps = float(cfg["rms_norm_eps"])

    def mixer(u):
        return attention(cfg, lp, _rms_norm(u, lp["norm1"], eps), hold)

    def feed(u):
        m = _rms_norm(u, lp["norm2"], eps)
        if ffn == "sparse_experts":
            return experts(cfg, lp, m, hold)
        tokens = m.reshape(-1, m.shape[-1])
        return _gated(tokens, lp["mlp_in"], lp["mlp_out"],
                      hold).reshape(m.shape)

    x = hyper_connection(cfg, lp, "hc_mix_", x, mixer, hold)
    return hyper_connection(cfg, lp, "hc_ffn_", x, feed, hold)


def _block(cfg, params, prefix, ffn, hold, x):
    """One layer over the streams ``x`` (b, t, n, C), a sequence at a time
    (sequences are independent), each recomputed in the backward pass."""
    lp = {k[len(prefix):]: v for k, v in params.items()
          if k.startswith(prefix)}
    layer = jax.checkpoint(functools.partial(_layer, cfg, ffn, hold))
    return lax.map(lambda row: layer(lp, row[None])[0], x)


def loss_fn(cfg, params, x, y, hold):
    """The mean cross-entropy over every position."""
    n = int(cfg["hc_mult"])
    table = params["embed" if cfg["tie_word_embeddings"] else "head"]
    e = params["embed"][x]
    h = jnp.stack([e] * n, axis=-2)
    for i, ffn in enumerate(layer_table(cfg)):
        h = _block(cfg, params, "l%d_" % i, ffn, hold, h)
    h = sum(h[..., i, :] for i in range(n))
    return jnp.mean(token_losses(cfg, h, params["norm_f"], table, y, hold))


@functools.lru_cache(maxsize=None)
def _reference_step_fn(cfg_json, variant, update):
    """The jitted reference step ``(params, momentum, x, y) -> (loss,
    gradient norms[, params, momentum])``.  With ``update`` the state is
    donated and its successor returned (float32 weights, gradients and
    momentum of the whole model are 9.1 GB at the cell's size); without, the
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
class Program(mla_moe.Program):
    """``HybridLM`` under ``DataParallelTrainer``'s mesh tier, as
    ``mla_moe.Program`` drives it, built from this family's leaves and
    weights (the float32 weights and their momentum are 6.1 GB at the
    cell's size)."""

    def __init__(self, config, size, mesh, seed):
        import mxnet_tpu as mx
        from mxnet_tpu.parallel import DataParallelTrainer, MeshPlan
        from mxnet_tpu.transformer import HybridLM, HybridLMConfig

        mx.random.seed(int(seed) & 0x7FFFFFFF)
        self._key = seeds.key(seed, stream=0)
        self._norms = _state_norms_fn(config, size)
        keys, sizes = program_keys(sized(config, size))
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


def build(config, size, mesh, seed):
    return Program(config, size, mesh, seed)
