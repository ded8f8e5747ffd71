"""The dense ``granitemoehybrid`` language model (IBM Granite 4.0-H):
Mamba-2 state-space layers beside grouped-query attention, every layer
followed by a gated SiLU feed-forward.

Three things live here, for every configuration whose ``family`` is
``granite_hybrid``:

* ``build`` — the system under test, through the program's public entry
  points: ``transformer.HybridLM`` under ``DataParallelTrainer(block, None,
  'sgd', mesh_plan=MeshPlan(data=chips), dtype=...)``.
* ``flops_per_item`` — the benchmark's own count of the arithmetic one
  trained sequence needs: 2 FLOPs per multiply-add, training = 3 x forward;
  the matrix products (every projection and the tied head), the products of
  the chunked state-space scan, and causal attention's scores and values.
  Recomputation is not counted.  ``ssd_scan_forward_work`` and
  ``ssd_scan_backward_work`` give the operations and bytes of one run of
  the scan alone, in the same terms (``layer_metrics/ssd_scan_roofline.py``).
* ``reference_readings`` — the plain reference: the same model, loss,
  gradients and SGD-momentum update in float32 ``jax.numpy``/``lax`` at
  matmul precision ``highest``.  It imports nothing of ``mxnet_tpu`` and is
  handed nothing the program made: the weights come from ``make_weights``
  (this file, from the seed), which ``build`` also loads into the program.

The equations, as the HF ``granitemoehybrid``/``mamba2`` modelling code has
them (``T`` tokens, hidden ``d``)::

    h = E[ids] * embedding_multiplier
    h = h + residual_multiplier * Mixer(RMSNorm(h))
    h = h + residual_multiplier * MLP(RMSNorm(h))          for every layer
    logits = RMSNorm(h) E^T / logits_scaling ; mean token cross-entropy
    MLP(x) = (silu(a) * b) W_out, [a, b] = x W_in
    attention: softmax(attention_multiplier q k^T) v, causal, no positions
    mamba: [z, xBC, dt] = x W_in; xBC = silu(conv1d(xBC)); dt = softplus(dt +
      dt_bias); S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T; y_t = S_t C_t +
      D x_t; out = (RMSNorm(y * silu(z)) * w) W_out

Departures of this reference from that code, none of which changes the
mathematics: the state-space layer is the **defining recurrence**, one
``lax.scan`` step a token (HF computes it chunk by chunk), and its backward
pass recomputes the states in blocks of time; attention is computed a block
of query rows at a time against every key (rows are independent); every
layer is recomputed in the backward pass, so that three steps at the cell's
size fit on one chip.  ``vocab_size`` is the number of embedding rows held
(a sliced vocabulary is a smaller vocabulary); ``time_step_limit`` is HF's
default (0, inf), which clamps nothing.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
from jax import lax

import seeds

FP8_MAX = 448.0            # largest finite float8_e4m3fn
ATTENTION_ROWS = 512       # query rows the reference attends at a time


# ---------------------------------------------------------------------------
# the model as plain data
# ---------------------------------------------------------------------------
def sized(config, size):
    """The configuration as it is run: ``size`` over ``config``."""
    return dict(config, **size)


def layer_table(cfg):
    """The mixer of every layer held: the first ``num_hidden_layers``
    entries of the published ``layer_types``."""
    return list(cfg["layer_types"][:int(cfg["num_hidden_layers"])])


def _layer_leaves(cfg, mixer):
    d, f = int(cfg["hidden_size"]), int(cfg["shared_intermediate_size"])
    if mixer == "mamba":
        h, p, n = (int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"]),
                   int(cfg["mamba_d_state"]))
        k = int(cfg["mamba_d_conv"])
        mix = [("ssm_in", (d, 2 * h * p + 2 * n + h)),
               ("ssm_conv_w", (k, h * p + 2 * n)),
               ("ssm_conv_b", (h * p + 2 * n,)),
               ("ssm_dt_bias", (h,)), ("ssm_a_log", (h,)), ("ssm_d", (h,)),
               ("ssm_norm", (h * p,)), ("ssm_out", (h * p, d))]
    else:
        hq, hkv = (int(cfg["num_attention_heads"]),
                   int(cfg["num_key_value_heads"]))
        e = d // hq
        mix = [("wq", (d, hq, e)), ("wk", (d, hkv, e)), ("wv", (d, hkv, e)),
               ("wo", (hq, e, d))]
    return [("norm1", (d,))] + mix + [
        ("norm2", (d,)), ("mlp_in", (d, 2 * f)), ("mlp_out", (f, d))]


def leaves(config, size):
    """[(name, kind, shape)] of every trained leaf, in the order
    ``HybridProgram.param_names`` lists them."""
    cfg = sized(config, size)
    d = int(cfg["hidden_size"])
    out = [("embed", "embed", (int(cfg["vocab_size"]), d))]
    for i, mixer in enumerate(layer_table(cfg)):
        out += [("l%d_%s" % (i, kind), kind, shape)
                for kind, shape in _layer_leaves(cfg, mixer)]
    out.append(("norm_f", "norm_f", (d,)))
    return out


def _draw(cfg, key, kind, shape):
    """One leaf from ``key`` (the configuration's ``assumed``
    ``initialisation``): projections normal over the root of their fan-in,
    the embedding normal times 0.02, norms and ``D`` one, the convolution
    uniform within one over the root of its width, ``A_log`` the log of
    1..heads, ``dt_bias`` the inverse softplus of a step log-uniform in
    [0.001, 0.1]."""
    f32 = jnp.float32
    if kind.startswith("norm") or kind in ("ssm_norm", "ssm_d"):
        return jnp.ones(shape, f32)
    if kind == "embed":
        return jax.random.normal(key, shape, f32) * 0.02
    if kind in ("ssm_conv_w", "ssm_conv_b"):
        bound = int(cfg["mamba_d_conv"]) ** -0.5
        return jax.random.uniform(key, shape, f32, -bound, bound)
    if kind == "ssm_a_log":
        return jnp.log(jnp.arange(1, shape[0] + 1, dtype=f32))
    if kind == "ssm_dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32, math.log(1e-3),
                                        math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    fan_in = shape[0] * shape[1] if kind == "wo" else shape[0]
    return jax.random.normal(key, shape, f32) / math.sqrt(fan_in)


def _drawn(config, size, key):
    """name -> leaf, every leaf of the seed's weights (traced)."""
    cfg = sized(config, size)
    return {name: _draw(cfg, jax.random.fold_in(key, i), kind, shape)
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
    length: the matrix products, the state-space scan's products in their
    chunked form (causal inside a chunk) and causal attention's.  Norms,
    the convolution of width 4, gates, the softmax and the loss are not
    counted."""
    cfg = sized(config, size)
    d, f = int(cfg["hidden_size"]), int(cfg["shared_intermediate_size"])
    t, v = int(cfg["seq_len"]), int(cfg["vocab_size"])
    h, p, n = (int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"]),
               int(cfg["mamba_d_state"]))
    chunk = min(int(cfg["mamba_chunk_size"]), t)
    hq, hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    e = d // hq
    mlp = d * 2 * f + f * d
    macs = d * v                                  # the tied head
    for mixer in layer_table(cfg):
        if mixer == "mamba":
            inner = h * p
            macs += d * (2 * inner + 2 * n + h) + inner * d
            macs += _scan_macs_per_token(n, inner, chunk)
        else:
            macs += d * (hq + 2 * hkv) * e + hq * e * d
            macs += 2 * hq * e * (t + 1) / 2      # scores and values
        macs += mlp
    return macs


def _scan_macs_per_token(n, inner, chunk):
    """Multiply-adds a token of the chunked scan's forward pass: the C.B
    scores and their products with x over the (chunk + 1) / 2 steps a token
    sees in its chunk; the state's update and read."""
    return (n + inner) * (chunk + 1) / 2 + 2 * inner * n


def _scan_work(config, size, backward):
    """(FLOPs, bytes) of one run of the chunked scan over a chip's batch,
    from shapes alone, whatever implements it: the multiply-adds
    ``forward_macs_per_token`` counts for the scan (causal inside a chunk;
    the backward pass twice the forward's), and every operand and result
    once in the type the scan is handed it.  Padding, the zero half of a
    decay matrix and an operand read twice are an implementation's loss,
    not its work.

    Forward: C, B (tokens x state) and x (tokens x heads x head width) in
    the compute type, dt and the summed log-decays (tokens x heads) in
    float32; y as x, and the state each chunk was handed (heads x head
    width x state a chunk, float32), which the backward pass reads.
    Backward: those, and dy as x; the gradient of each of the five."""
    cfg = sized(config, size)
    h, p, n = (int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"]),
               int(cfg["mamba_d_state"]))
    t = int(cfg["seq_len"])
    chunk = min(int(cfg["mamba_chunk_size"]), t)
    tokens = int(cfg["batch_per_chip"]) * t
    inner = h * p
    wide = jnp.dtype(cfg["dtype"]).itemsize
    macs = tokens * _scan_macs_per_token(n, inner, chunk)
    five = tokens * ((2 * n + inner) * wide + 2 * h * 4)    # C, B, x; dt, cum
    y = tokens * inner * wide                               # dy as well
    states = tokens // chunk * inner * n * 4
    if not backward:
        return 2 * macs, five + y + states
    return 2 * 2 * macs, (five + states + y) + five


def ssd_scan_forward_work(config, size):
    return _scan_work(config, size, backward=False)


def ssd_scan_backward_work(config, size):
    return _scan_work(config, size, backward=True)


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


# how a variant holds the operands and the result of every matrix product
# and the operands of the state-space scan; the rest stays float32
HOLD = {"float32": lambda a: a,
        "bfloat16": _both_ways(_round_bf16),
        "fp8": _both_ways(_round_fp8)}


def _product(spec, a, b, hold):
    return hold(jnp.einsum(spec, hold(a), hold(b),
                           precision=lax.Precision.HIGHEST))


def _rms_norm(x, weight, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * weight


def _time_block(t):
    """The largest divisor of ``t`` not above its root: the states of the
    recurrence are kept at every such step and recomputed between them."""
    return max(b for b in range(1, math.isqrt(t) + 1) if t % b == 0)


def _recurrence(x, dt, a, B, C):
    """``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t``:
    the defining recurrence, one step a token.  x (b, t, h, p); dt
    (b, t, h); a (h,); B, C (b, t, n)."""
    b, t, h, p = x.shape

    def step(state, inp):
        dt_t, x_t, b_t, c_t = inp
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        return state, jnp.einsum("bhpn,bn->bhp", state, c_t,
                                 precision=lax.Precision.HIGHEST)

    @jax.checkpoint
    def block(state, inps):
        return lax.scan(step, state, inps)

    size = _time_block(t)
    seq = tuple(jnp.moveaxis(v, 1, 0).reshape((t // size, size)
                                              + v.shape[:1] + v.shape[2:])
                for v in (dt, x, B, C))
    _, y = lax.scan(block, jnp.zeros((b, h, p, B.shape[-1]), x.dtype), seq)
    return jnp.moveaxis(y.reshape((t, b, h, p)), 0, 1)


def _mamba(cfg, lp, x, hold):
    h, p, n = (int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"]),
               int(cfg["mamba_d_state"]))
    width = int(cfg["mamba_d_conv"])
    inner = h * p
    b, t, _ = x.shape
    proj = _product("btd,de->bte", x, lp["ssm_in"], hold)
    z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * n], axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    xbc = lp["ssm_conv_b"] + sum(padded[:, k:k + t] * lp["ssm_conv_w"][k]
                                 for k in range(width))
    xbc = jax.nn.silu(xbc)
    xs, B, C = jnp.split(xbc, [inner, inner + n], axis=-1)
    xs = hold(xs).reshape(b, t, h, p)
    dt = hold(jax.nn.softplus(dt + lp["ssm_dt_bias"]))
    y = _recurrence(xs, dt, -jnp.exp(lp["ssm_a_log"]), hold(B), hold(C))
    y = (y + xs * lp["ssm_d"][:, None]).reshape(b, t, inner)
    y = _rms_norm(y * jax.nn.silu(z), lp["ssm_norm"],
                  float(cfg["rms_norm_eps"]))
    return _product("bte,ed->btd", y, lp["ssm_out"], hold)


def _attention(cfg, lp, x, hold):
    scale = float(cfg["attention_multiplier"])
    b, t, _ = x.shape
    q = _product("btd,dhe->bthe", x, lp["wq"], hold)
    k = _product("btd,dhe->bthe", x, lp["wk"], hold)
    v = _product("btd,dhe->bthe", x, lp["wv"], hold)
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
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
    out = jnp.moveaxis(out, 0, 1).reshape(q.shape)
    return _product("bthe,hed->btd", out, lp["wo"], hold)


def _layer(cfg, mixer, hold, lp, h):
    eps, scale = float(cfg["rms_norm_eps"]), float(cfg["residual_multiplier"])
    mix = _mamba if mixer == "mamba" else _attention
    h = h + scale * mix(cfg, lp, _rms_norm(h, lp["norm1"], eps), hold)
    m = _rms_norm(h, lp["norm2"], eps)
    a, b = jnp.split(_product("btd,df->btf", m, lp["mlp_in"], hold), 2,
                     axis=-1)
    return h + scale * _product("btf,fd->btd", jax.nn.silu(a) * b,
                                lp["mlp_out"], hold)


def final_hidden(cfg, params, x, hold):
    """The residual stream after the last layer and the final norm, from
    the rows of ``params["embed"]`` that ``x`` names."""
    h = params["embed"][x] * float(cfg["embedding_multiplier"])
    for i, mixer in enumerate(layer_table(cfg)):
        prefix = "l%d_" % i
        lp = {k[len(prefix):]: v for k, v in params.items()
              if k.startswith(prefix)}
        h = jax.checkpoint(functools.partial(_layer, cfg, mixer, hold))(lp, h)
    return _rms_norm(h, params["norm_f"], float(cfg["rms_norm_eps"]))


def head_loss(cfg, h, table, y, hold):
    """Mean token cross-entropy of ``h table^T / logits_scaling`` over the
    rows of ``table`` (the tied embedding), float32."""
    logits = _product("btd,vd->btv", h, table, hold) \
        / float(cfg["logits_scaling"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1))


def loss_fn(cfg, params, x, y, hold):
    return head_loss(cfg, final_hidden(cfg, params, x, hold),
                     params["embed"], y, hold)


def _leaf_norms(tree):
    return {k: _norm(v) for k, v in tree.items()}


@functools.lru_cache(maxsize=None)
def _reference_step_fn(cfg_json, variant, update):
    """The jitted reference step ``(params, momentum, x, y) -> (loss,
    gradient norms[, params, momentum])``.  With ``update`` the state is
    donated and its successor returned (float32 weights, gradients and
    momentum of the whole model are 9.3 GB at the cell's size); without, the
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
    params = make_weights(config, size, seed)
    momentum = jax.tree_util.tree_map(jnp.zeros_like, params)
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
    weights and their momentum are 6.2 GB at the cell's size)."""

    def __init__(self, config, size, mesh, seed):
        import mxnet_tpu as mx
        from jax.sharding import NamedSharding, PartitionSpec
        from mxnet_tpu.parallel import DataParallelTrainer, MeshPlan
        from mxnet_tpu.transformer import HybridLM, HybridLMConfig

        mx.random.seed(int(seed) & 0x7FFFFFFF)
        cfg = sized(config, size)
        self._key = seeds.key(seed, stream=0)
        self._norms = _state_norms_fn(config, size)
        model = HybridLMConfig.from_hf(
            cfg, seq_len=int(cfg["seq_len"]))
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
