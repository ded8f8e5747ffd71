"""The ``deepseek_v3`` family of language models as ``JoyAI-LLM-Flash`` has
it (``model_type`` ``joyai_llm_flash``): latent attention with rotary
positions in every layer, a leading dense gated feed-forward and sparse
experts after it, an untied head, and one next-next-token prediction
module.

Three things live here, for every configuration whose ``family`` is
``mla_moe``:

* ``build`` — the system under test, through the program's public entry
  points: ``transformer.HybridLM`` under ``DataParallelTrainer(block, None,
  'sgd', mesh_plan=MeshPlan(data=chips), dtype=...)``.
* ``flops_per_item`` — the benchmark's own count of the arithmetic one
  trained sequence needs, from shapes alone: 2 FLOPs per multiply-add,
  training = 3 x forward; every projection, causal attention's scores and
  values, the router, the shared expert, the held experts at the rows an
  even router sends them (``experts a token x held / published`` of the
  token-slots), the prediction module and both heads.  Recomputation is not
  counted.
* ``reference_readings`` — the plain reference: the same model, both
  losses, gradients and SGD-momentum update in float32 ``jax.numpy``/``lax``
  at matmul precision ``highest``.  It imports nothing of ``mxnet_tpu`` and
  is handed nothing the program made: the weights come from
  ``make_weights`` (this file, from the seed), which ``build`` also loads
  into the program.

The equations, as the HF ``deepseek_v3`` modelling code has them (hidden
``d``; ``h`` heads)::

    h = E[ids]
    h = h + MLA(RMSNorm(h)) ;  h = h + FFN_i(RMSNorm(h))
        FFN_i: the gated feed-forward for i < first_k_dense_replace, sparse
        experts after
    MLA(x):  c_q = RMSNorm(x W_qa) ; q = c_q W_qb -> h x (nope | rope)
             [c_kv | k_r] = x W_kva ; c_kv = RMSNorm(c_kv)
             [k_nope | v] = c_kv W_kvb -> h x (nope | v)
             q_r, k_r = RoPE(., rope_theta, pairs (2j, 2j+1)); k_r is one
             head, shared by all
             o = softmax([q_nope|q_r][k_nope|k_r]^T / sqrt(nope + rope),
                 causal) v ;  out = o W_o
    experts(x): s = sigmoid(x W_r^T) ; chosen = top-k of (s + b)
             w = s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor
             out = Shared(x) + sum over the chosen e held here of
                   w_e Expert_e(x)
             Expert, Shared, dense: (silu(a) * b) W_out, [a, b] = x W_in
    main loss = mean CE(RMSNorm(h_L) W_head^T, y)
    module:  u_i = W_eh [RMSNorm_h(h_L,i) ; RMSNorm_e(E[y_i])] through one
             more MLA + experts layer, its own final RMSNorm, the same E and
             W_head; target y_{i+1}; a row's last position left out
    loss = main + mtp_loss_weight * module loss

**The share.**  ``n_routed_experts`` counts the experts held here and
``expert_shard`` says which (``index`` of ``of``): the router is as wide as
published (``n_routed_experts x of``), and what the experts of the other
chips would have added is left out, here as in the program.  ``vocab_size``
is the number of embedding and head rows held (a sliced vocabulary is a
smaller vocabulary).

Departures of this reference from that code, none of which changes the
mathematics: the experts are the **plain form** (every expert held runs
over every token, and the choice masks its result; HF gathers each expert's
tokens); the rotary pairs are turned where they stand (HF moves the two
columns of a pair apart first, in queries and keys alike, which the scores
cannot see); attention is computed a block of query rows at a time against
every key (rows are independent), and every layer and each head a sequence
at a time (sequences are independent), recomputed in the backward pass: so
that three steps at the cell's size fit on one chip.
``e_score_correction_bias`` (``b``) is a leaf drawn from the seed whose
gradient is exactly zero; its published update (a step by the sign of each
expert's load) is not part of the step.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec

import seeds

FP8_MAX = 448.0            # largest finite float8_e4m3fn
ATTENTION_ROWS = 128       # query rows the reference attends at a time


# ---------------------------------------------------------------------------
# the model as plain data
# ---------------------------------------------------------------------------
def sized(config, size):
    """The configuration as it is run: ``size`` over ``config``."""
    return dict(config, **size)


def shard(cfg):
    """(index, of): which of the equal shares of the routed experts is held
    here; the router's width is ``n_routed_experts x of``."""
    s = cfg.get("expert_shard") or {"index": 0, "of": 1}
    return int(s["index"]), int(s["of"])


def program_keys(cfg):
    """``cfg`` as ``HybridLMConfig.from_hf`` takes it, ``(config, sizes)``:
    a configuration file counts the experts held under ``n_routed_experts``;
    the program's key is the published one, the router's width, and which
    share is held is an argument beside the sizes."""
    index, of = shard(cfg)
    return (dict(cfg, n_routed_experts=int(cfg["n_routed_experts"]) * of),
            {"seq_len": int(cfg["seq_len"]),
             "attention_block": int(cfg["attention_block"]),
             "expert_shard": (index, of)})


def layer_table(cfg):
    """The feed-forward of every layer held (the mixer is latent attention
    throughout)."""
    layers = int(cfg["num_hidden_layers"])
    dense = min(int(cfg["first_k_dense_replace"]), layers)
    return ["gated_mlp"] * dense + ["sparse_experts"] * (layers - dense)


def _layer_leaves(cfg, ffn):
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    qr, kvr = int(cfg["q_lora_rank"]), int(cfg["kv_lora_rank"])
    nope, rope, v = (int(cfg["qk_nope_head_dim"]),
                     int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"]))
    mix = [("wq_a", (d, qr)), ("norm_q", (qr,)),
           ("wq_b", (qr, h, nope + rope)), ("wkv_a", (d, kvr + rope)),
           ("norm_kv", (kvr,)), ("wkv_b", (kvr, h, nope + v)),
           ("wo", (h, v, d))]
    if ffn == "sparse_experts":
        f = int(cfg["moe_intermediate_size"])
        held = int(cfg["n_routed_experts"])
        wide = held * shard(cfg)[1]
        fs = int(cfg["n_shared_experts"]) * f
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
    for i, ffn in enumerate(layer_table(cfg)):
        out += [("l%d_%s" % (i, kind), kind, shape)
                for kind, shape in _layer_leaves(cfg, ffn)]
    out.append(("norm_f", "norm_f", (d,)))
    if not cfg["tie_word_embeddings"]:
        out.append(("head", "head", (rows, d)))
    if int(cfg["num_nextn_predict_layers"]):
        out += [("mtp_norm_h", "norm_h", (d,)), ("mtp_norm_e", "norm_e", (d,)),
                ("mtp_eh_proj", "eh_proj", (2 * d, d))]
        out += [("mtp_" + kind, kind, shape)
                for kind, shape in _layer_leaves(cfg, "sparse_experts")]
        out.append(("mtp_norm_f", "norm_f", (d,)))
    return out


def _draw(key, kind, shape):
    """One leaf from ``key`` (the configuration's ``assumed``
    ``initialisation``): projections normal over the root of their fan-in,
    embedding and head normal times 0.02, norms one, the router's choosing
    bias uniform within 0.1."""
    f32 = jnp.float32
    if kind.startswith("norm"):
        return jnp.ones(shape, f32)
    if kind in ("embed", "head"):
        return jax.random.normal(key, shape, f32) * 0.02
    if kind == "router_bias":
        return jax.random.uniform(key, shape, f32, -0.1, 0.1)
    fan_in = {"wo": shape[0] * shape[1], "router": shape[-1],
              "moe_in": shape[1], "moe_out": shape[1]}.get(kind, shape[0])
    return jax.random.normal(key, shape, f32) / math.sqrt(fan_in)


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


def _norm(a):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))


def _state_norms_fn(config, size):
    """The jitted ``(params, momentum, key) -> (first-gradient norms, update
    norms, momentum norms)`` by leaf: the gradient the optimizer got in the
    step that made ``momentum`` from nought (``m1 = -lr (g + wd w0)``), the
    change of every leaf from the seed's weights, and the momentum itself
    (its change from nought).  The seed's weights are drawn again leaf by
    leaf inside the program, so that no second copy of them is kept."""
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
    length, from shapes alone.  Norms, rotary turns, gates, the softmax, the
    sort and the losses are not counted."""
    cfg = sized(config, size)
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    qr, kvr = int(cfg["q_lora_rank"]), int(cfg["kv_lora_rank"])
    nope, rope, v = (int(cfg["qk_nope_head_dim"]),
                     int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"]))
    t, rows = int(cfg["seq_len"]), int(cfg["vocab_size"])
    f, held = int(cfg["moe_intermediate_size"]), int(cfg["n_routed_experts"])
    wide = held * shard(cfg)[1]
    mixer = (d * qr + qr * h * (nope + rope) + d * (kvr + rope)
             + kvr * h * (nope + v) + h * v * d
             + h * (nope + rope + v) * (t + 1) / 2)     # scores and values
    feed = {"gated_mlp": 3 * d * int(cfg["intermediate_size"]),
            "sparse_experts": (
                d * wide + 3 * d * int(cfg["n_shared_experts"]) * f
                + int(cfg["num_experts_per_tok"]) * held / wide * 3 * d * f)}
    macs = sum(mixer + feed[ffn] for ffn in layer_table(cfg)) + d * rows
    if int(cfg["num_nextn_predict_layers"]):
        macs += 2 * d * d + mixer + feed["sparse_experts"] + d * rows
    return macs


def flops_per_item(config, size):
    """FLOPs one trained sequence needs: 2 per multiply-add, the backward
    pass twice the forward's, ``seq_len`` tokens."""
    cfg = sized(config, size)
    return 3 * 2 * forward_macs_per_token(config, size) * int(cfg["seq_len"])


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def _round_fp8(x):
    scale = FP8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale


def _round_bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _both_ways(round_fn):
    """``round_fn`` applied to a value on the way forward and to its
    cotangent on the way back."""
    @jax.custom_vjp
    def held(x):
        return round_fn(x)

    held.defvjp(lambda x: (round_fn(x), None),
                lambda _, g: (round_fn(g),))
    return held


# how a variant holds the operands and the result of every matrix product;
# the rest stays float32
HOLD = {"float32": lambda a: a,
        "bfloat16": _both_ways(_round_bf16),
        "fp8": _both_ways(_round_fp8)}


def _product(spec, a, b, hold):
    return hold(jnp.einsum(spec, hold(a), hold(b),
                           precision=lax.Precision.HIGHEST))


def _rms_norm(x, weight, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * weight


def _gated(x, w_in, w_out, hold):
    a, b = jnp.split(_product("td,df->tf", x, w_in, hold), 2, axis=-1)
    return _product("tf,fd->td", jax.nn.silu(a) * b, w_out, hold)


def rope(x, theta):
    """Rotary positions over ``x`` (b, t, ..., r): columns ``(2j, 2j+1)`` of
    position ``p`` turn by ``p theta^(-2j/r)``."""
    r, t = x.shape[-1], x.shape[1]
    angle = (jnp.arange(t, dtype=jnp.float32)[:, None]
             * theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    angle = angle.reshape((1, t) + (1,) * (x.ndim - 3) + (r // 2,))
    first, second = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([first * jnp.cos(angle) - second * jnp.sin(angle),
                        second * jnp.cos(angle) + first * jnp.sin(angle)],
                       axis=-1)
    return turned.reshape(x.shape)


def attention(cfg, lp, x, hold):
    """Latent attention over the normed stream ``x`` (b, t, d)."""
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    nope, kvr = int(cfg["qk_nope_head_dim"]), int(cfg["kv_lora_rank"])
    b, t, _ = x.shape
    c_q = _rms_norm(_product("btd,dr->btr", x, lp["wq_a"], hold),
                    lp["norm_q"], eps)
    q = _product("btr,rhe->bthe", c_q, lp["wq_b"], hold)
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
    return _product("bthe,hed->btd", out, lp["wo"], hold)


def router(cfg, lp, x, hold):
    """``(chosen, weights)`` of tokens ``x`` (T, d): (T, k) each."""
    s = jax.nn.sigmoid(_product("td,ed->te", x, lp["router"], hold))
    _, chosen = lax.top_k(s + lp["router_bias"],
                          int(cfg["num_experts_per_tok"]))
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * float(cfg["routed_scaling_factor"])


def routed_part(cfg, lp, x, hold):
    """What the experts held here add for tokens ``x`` (T, d), in the plain
    form: every one of them over every token, its result weighted by the
    token's choice of it (nought where it was not chosen)."""
    held = int(cfg["n_routed_experts"])
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


def _layer(cfg, ffn, hold, lp, h):
    eps = float(cfg["rms_norm_eps"])
    h = h + attention(cfg, lp, _rms_norm(h, lp["norm1"], eps), hold)
    m = _rms_norm(h, lp["norm2"], eps)
    if ffn == "sparse_experts":
        return h + experts(cfg, lp, m, hold)
    tokens = m.reshape(-1, m.shape[-1])
    return h + _gated(tokens, lp["mlp_in"], lp["mlp_out"],
                      hold).reshape(h.shape)


def _block(cfg, params, prefix, ffn, hold, h):
    """One layer over the stream ``h`` (b, t, d), a sequence at a time
    (sequences are independent), each recomputed in the backward pass."""
    lp = {k[len(prefix):]: v for k, v in params.items()
          if k.startswith(prefix)}
    layer = jax.checkpoint(functools.partial(_layer, cfg, ffn, hold))
    return lax.map(lambda row: layer(lp, row[None])[0], h)


def token_losses(cfg, h, norm, table, y, hold):
    """Cross-entropy of every position of ``h`` (b, t, d) against ``y`` over
    the rows of ``table``, through the final norm ``norm``, float32; a
    sequence at a time, recomputed in the backward pass."""
    @jax.checkpoint
    def losses(row):
        h, y = row
        logits = _product("td,vd->tv",
                          _rms_norm(h, norm, float(cfg["rms_norm_eps"])),
                          table, hold)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]

    return lax.map(losses, (h, y))


def sequence_losses(cfg, params, x, y, hold):
    """Of sequences ``x`` (b, t) with labels ``y``: the main model's loss at
    every position (b, t) and the prediction module's at every position but
    a row's last (b, t - 1; none without a module)."""
    eps = float(cfg["rms_norm_eps"])
    table = params["embed" if cfg["tie_word_embeddings"] else "head"]
    h = params["embed"][x]
    for i, ffn in enumerate(layer_table(cfg)):
        h = _block(cfg, params, "l%d_" % i, ffn, hold, h)
    main = token_losses(cfg, h, params["norm_f"], table, y, hold)
    if not int(cfg["num_nextn_predict_layers"]):
        return main, jnp.zeros((x.shape[0], 0), main.dtype)
    both = jnp.concatenate(
        [_rms_norm(h, params["mtp_norm_h"], eps),
         _rms_norm(params["embed"][y], params["mtp_norm_e"], eps)], axis=-1)
    u = _product("bte,ed->btd", both, params["mtp_eh_proj"], hold)
    u = _block(cfg, params, "mtp_", "sparse_experts", hold, u)
    # position i is asked for y[i + 1]; the last has none
    return main, token_losses(cfg, u[:, :-1], params["mtp_norm_f"], table,
                              y[:, 1:], hold)


def loss_fn(cfg, params, x, y, hold):
    """``main + mtp_loss_weight * module``, each a mean over its
    positions."""
    main, ahead = sequence_losses(cfg, params, x, y, hold)
    loss = jnp.mean(main)
    if ahead.size:
        loss = loss + float(cfg["mtp_loss_weight"]) * jnp.mean(ahead)
    return loss


def _leaf_norms(tree):
    return {k: _norm(v) for k, v in tree.items()}


@functools.lru_cache(maxsize=None)
def _reference_step_fn(cfg_json, variant, update):
    """The jitted reference step ``(params, momentum, x, y) -> (loss,
    gradient norms[, params, momentum])``.  With ``update`` the state is
    donated and its successor returned (float32 weights, gradients and
    momentum of the whole model are 8.2 GB at the cell's size); without, the
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
    # the step hands it back: its first call then sees the types its later
    # calls see, and the step is compiled once, not twice
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
    weights and their momentum are 5.4 GB at the cell's size)."""

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
