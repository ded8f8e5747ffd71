"""A language model whose layers come from a table: Mamba-2 state-space
mixers beside grouped-query attention, each followed by a gated
feed-forward, on the ``mesh_plan`` path (docs/transformer.md "The layer
table").

:class:`HybridLM` is a block for ``DataParallelTrainer(block, None, 'sgd',
mesh_plan=MeshPlan())``: ``mesh_program(plan)`` gives a
:class:`HybridProgram`, which ``transformer/step.py`` trains through the
same ``grads_part``/``update_part`` as :class:`~.model.MeshProgram`.  The
equations are those of the ``granitemoehybrid`` family (dense, no experts)::

    h = E[ids] * embedding_multiplier
    h = h + residual_multiplier * Mixer(RMSNorm(h))      per layer, mixer by
    h = h + residual_multiplier * MLP(RMSNorm(h))        ``layer_types[i]``
    logits = RMSNorm(h) E^T / logits_scaling             (tied embedding)
    MLP(x) = (silu(a) * b) W_out,  [a, b] = x W_in
    attention: softmax(attention_multiplier q k^T) v, causal, no positions,
               ``kv_heads`` key-value heads each serving ``heads/kv_heads``
               query heads
    mamba: ``transformer/ssm.py``

No layer is divided: a plan with a ``model``, ``sequence`` or ``pipe`` axis
is refused.  The ``data`` axis works as for every mesh program (the step
wrapper owns the one gradient exchange).  ``vocab_size`` is the number of
embedding rows held here: ids, logits and the loss are over those rows.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..telemetry import compiles as _compiles
from . import layers as L
from . import ssm
from .model import ProgramLayout

__all__ = ["HybridLMConfig", "HybridLM", "HybridProgram", "LAYER_LEAVES",
           "causal_gqa_attention", "rms_norm", "gated_mlp",
           "kept_product_bytes", "keeps_products"]

MIXERS = ("mamba", "attention")
# what a training step holds on the mesh tier, bytes a parameter: float32
# master 4, compute copy 2, float32 gradient 4, float32 momentum 4
RESIDENT_BYTES_PER_PARAM = 14
# the share of the device's memory the reckoning of `keeps_products` may fill
ROOM = 0.9


class HybridLMConfig:
    """Sizes of a :class:`HybridLM`.  ``layer_types`` is the table: one
    mixer kind a layer.  :meth:`from_hf` reads the keys of a published
    ``granitemoehybrid`` ``config.json``."""

    def __init__(self, vocab_size=64, d_model=32, layer_types=("mamba",
                 "attention"), d_ff=64, n_heads=4, n_kv_heads=2, head_dim=8,
                 attention_multiplier=None, ssm_heads=4, ssm_head_dim=16,
                 ssm_state=8, ssm_conv=4, ssm_chunk=8, norm_eps=1e-5,
                 embedding_multiplier=1.0, residual_multiplier=1.0,
                 logits_scaling=1.0, seq_len=32, attention_block=512,
                 init_seed=0, init_scale=0.02):
        self.vocab_size = int(vocab_size)
        self.d_model = int(d_model)
        self.layer_types = tuple(layer_types)
        self.d_ff = int(d_ff)
        self.n_heads = int(n_heads)
        self.n_kv_heads = int(n_kv_heads)
        self.head_dim = int(head_dim)
        self.attention_multiplier = float(
            self.head_dim ** -0.5 if attention_multiplier is None
            else attention_multiplier)
        self.ssm_heads = int(ssm_heads)
        self.ssm_head_dim = int(ssm_head_dim)
        self.ssm_state = int(ssm_state)
        self.ssm_conv = int(ssm_conv)
        self.ssm_chunk = int(ssm_chunk)
        self.norm_eps = float(norm_eps)
        self.embedding_multiplier = float(embedding_multiplier)
        self.residual_multiplier = float(residual_multiplier)
        self.logits_scaling = float(logits_scaling)
        self.seq_len = int(seq_len)
        self.attention_block = int(attention_block)
        self.init_seed = int(init_seed)
        self.init_scale = float(init_scale)
        unknown = set(self.layer_types) - set(MIXERS)
        if unknown or not self.layer_types:
            raise ValueError("layer_types must name mixers of %s, got %r"
                             % (MIXERS, layer_types))
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads %d must divide by n_kv_heads %d"
                             % (self.n_heads, self.n_kv_heads))

    @classmethod
    def from_hf(cls, config, **sizes):
        """From the keys of a ``granitemoehybrid`` ``config.json``: the first
        ``num_hidden_layers`` entries of ``layer_types`` are the layers.
        ``sizes`` are the arguments the file does not hold (``seq_len``,
        ``attention_block``, ...)."""
        if config.get("num_local_experts"):
            raise ValueError("sparse experts are not implemented")
        if config.get("position_embedding_type", "nope") != "nope":
            raise ValueError("only position_embedding_type 'nope' is "
                             "implemented")
        if config.get("mamba_n_groups", 1) != 1:
            raise ValueError("only mamba_n_groups 1 is implemented")
        d = int(config["hidden_size"])
        heads = int(config["num_attention_heads"])
        ssm_heads = int(config["mamba_n_heads"])
        ssm_head_dim = int(config["mamba_d_head"])
        if ssm_heads * ssm_head_dim != int(config["mamba_expand"]) * d:
            raise ValueError("mamba_n_heads x mamba_d_head is not "
                             "mamba_expand x hidden_size")
        return cls(
            vocab_size=config["vocab_size"], d_model=d,
            layer_types=config["layer_types"][
                :int(config["num_hidden_layers"])],
            d_ff=config["shared_intermediate_size"], n_heads=heads,
            n_kv_heads=config["num_key_value_heads"],
            head_dim=config.get("head_dim") or d // heads,
            attention_multiplier=config["attention_multiplier"],
            ssm_heads=ssm_heads, ssm_head_dim=ssm_head_dim,
            ssm_state=config["mamba_d_state"],
            ssm_conv=config["mamba_d_conv"],
            ssm_chunk=config["mamba_chunk_size"],
            norm_eps=config["rms_norm_eps"],
            embedding_multiplier=config["embedding_multiplier"],
            residual_multiplier=config["residual_multiplier"],
            logits_scaling=config["logits_scaling"], **sizes)

    @property
    def ssm_inner(self):
        return self.ssm_heads * self.ssm_head_dim

    def describe(self):
        return {k: getattr(self, k) for k in
                ("vocab_size", "d_model", "layer_types", "d_ff", "n_heads",
                 "n_kv_heads", "head_dim", "ssm_heads", "ssm_head_dim",
                 "ssm_state", "ssm_conv", "ssm_chunk", "seq_len",
                 "init_seed")}


def _layer_leaves(cfg, mixer):
    """[(kind, shape)] of one layer's leaves, in declaration order."""
    d, f = cfg.d_model, cfg.d_ff
    if mixer == "mamba":
        inner, n, h = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_heads
        mix = [("ssm_in", (d, 2 * inner + 2 * n + h)),
               ("ssm_conv_w", (cfg.ssm_conv, inner + 2 * n)),
               ("ssm_conv_b", (inner + 2 * n,)),
               ("ssm_dt_bias", (h,)), ("ssm_a_log", (h,)), ("ssm_d", (h,)),
               ("ssm_norm", (inner,)), ("ssm_out", (inner, d))]
    else:
        hq, hkv, e = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        mix = [("wq", (d, hq, e)), ("wk", (d, hkv, e)), ("wv", (d, hkv, e)),
               ("wo", (hq, e, d))]
    return [("norm1", (d,))] + mix + [
        ("norm2", (d,)), ("mlp_in", (d, 2 * f)), ("mlp_out", (f, d))]


# mixer kind -> the kinds of its layer's leaves (docs/transformer.md)
LAYER_LEAVES = {mixer: tuple(k for k, _ in _layer_leaves(
    HybridLMConfig(), mixer)) for mixer in MIXERS}


class HybridLM:
    """The block handed to ``DataParallelTrainer(mesh_plan=...)``.
    ``params`` (name -> float32 array, every leaf of
    ``mesh_program(plan).param_names``) takes the place of the seeded
    initialisation, as a checkpoint's weights would; the arrays are handed
    to the trainer, whose update donates them."""

    def __init__(self, cfg, params=None):
        if not isinstance(cfg, HybridLMConfig):
            cfg = HybridLMConfig(**cfg)
        self.cfg = cfg
        self._params = params

    def mesh_program(self, plan):
        return HybridProgram(self.cfg, plan, params=self._params)


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
    a, b = jnp.split(checkpoint_name(x @ lp["mlp_in"], ssm.PROJECTION), 2,
                     axis=-1)
    return (jax.nn.silu(a) * b) @ lp["mlp_out"]


def _product_widths(cfg, mixer):
    """Widths of the tagged projection products of one layer."""
    if mixer == "mamba":
        mix = [2 * cfg.ssm_inner + 2 * cfg.ssm_state + cfg.ssm_heads,
               cfg.d_model]
    else:
        mix = [cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim,
               cfg.n_kv_heads * cfg.head_dim, cfg.d_model]
    return mix + [2 * cfg.d_ff]


def kept_product_bytes(cfg, batch, seq, dtype):
    """Bytes of every layer's tagged projection products over a local
    chunk of ``batch x seq`` tokens in ``dtype``."""
    widths = sum(sum(_product_widths(cfg, m)) for m in cfg.layer_types)
    return batch * seq * widths * jnp.dtype(dtype).itemsize


def _layer_live_bytes(cfg, mixer, batch, seq, dtype):
    """One layer's live set while its backward pass runs, reckoned from
    shapes: every intermediate of the layer once in ``dtype``, and four
    float32 arrays of the mixer's scores (the scan's decay matrix of every
    chunk and its masked product with ``C B^T``, or one block of query rows
    against the keys; each with its gradient).  A scan that runs as the
    kernel pair (``ssm.scan_kernel_tiles``) has no such array."""
    d, f = cfg.d_model, cfg.d_ff
    if mixer == "mamba":
        inner, n = cfg.ssm_inner, cfg.ssm_state
        chunks = -(-seq // cfg.ssm_chunk)
        widths = 2 * (inner + 2 * n) + 3 * inner
        scores = 0 if ssm.scan_kernel_tiles(cfg, dtype) else \
            batch * chunks * cfg.ssm_heads * cfg.ssm_chunk ** 2
    else:
        widths = cfg.n_heads * cfg.head_dim
        scores = batch * cfg.n_heads * min(cfg.attention_block, seq) * seq
    widths += sum(_product_widths(cfg, mixer)) + 4 * d + f
    return (batch * seq * widths * jnp.dtype(dtype).itemsize
            + 4 * scores * 4)


def keeps_products(cfg, n_params, batch, seq, dtype, bytes_limit):
    """Whether a step over a local chunk of ``batch x seq`` tokens keeps
    its layers' projection products for the backward pass: they are kept
    where the resident state (:data:`RESIDENT_BYTES_PER_PARAM` a parameter,
    an over-count), the kept products and the largest layer's live set fit
    in :data:`ROOM` of ``bytes_limit``, the device's memory.  A device that
    reports no limit (``None``) keeps.  A pure function of its arguments:
    one shape on one kind of device compiles one program."""
    if bytes_limit is None:
        return True
    live = max(_layer_live_bytes(cfg, m, batch, seq, dtype)
               for m in set(cfg.layer_types))
    held = (RESIDENT_BYTES_PER_PARAM * n_params
            + kept_product_bytes(cfg, batch, seq, dtype) + live)
    return held <= ROOM * bytes_limit


def _device_bytes_limit():
    """The memory of the device a traced program will run on, as its
    allocator reports it, or None (the CPU reports none).  Nothing else of
    the allocator's state is read: what is in use differs from run to run."""
    stats = jax.local_devices()[0].memory_stats() or {}
    return stats.get("bytes_limit")


def _attend_rows(q, k, v, scale, start):
    """Rows ``start ..`` of causal attention against the keys up to the
    last of them.  q (b, r, kv, g, e); k, v (b, s, kv, e)."""
    scores = jnp.einsum("bqkge,bske->bkgqs", q, k,
                        preferred_element_type=jnp.float32) * scale
    rows = start + jnp.arange(q.shape[1])
    seen = rows[:, None] >= jnp.arange(k.shape[1])[None, :]
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bkgqs,bske->bqkge", probs.astype(v.dtype), v)


def causal_gqa_attention(q, k, v, scale, block):
    """Causal grouped-query attention, ``block`` query rows at a time so
    that no more than ``block x t`` scores a head are live, and no key past
    a block's last row is read.  q (b, t, heads, e); k, v (b, t, kv_heads,
    e), each key-value head serving ``heads / kv_heads`` query heads in
    order.  The scores are recomputed in the backward pass."""
    b, t, heads, e = q.shape
    kv = k.shape[2]
    q = q.reshape(b, t, kv, heads // kv, e)
    rows = jax.checkpoint(_attend_rows, static_argnums=(3, 4))
    out = [rows(q[:, start:start + block], k[:, :start + block],
                v[:, :start + block], scale, start)
           for start in range(0, t, block)]
    return jnp.concatenate(out, axis=1).reshape(b, t, heads, e)


class HybridProgram(ProgramLayout):
    """One (config, plan) pair's program, with the interface
    ``transformer/step.py`` and the trainer's mesh tier read:
    ``param_names``, the layout, ``init_params`` and ``loss_replica``."""

    pipelined = False
    n_micro = None
    pipe_replicated = frozenset()

    def __init__(self, cfg, plan, params=None):
        from jax.sharding import PartitionSpec as P
        for axis in ("model", "sequence", "pipe"):
            if plan.present(axis):
                raise ValueError(
                    "HybridLM divides no layer: the plan's %s axis must be "
                    "1, got %d" % (axis, plan.size(axis)))
        self.cfg = cfg
        self.plan = plan
        specs = [("embed", "embed", (cfg.vocab_size, cfg.d_model))]
        for i, mixer in enumerate(cfg.layer_types):
            specs += [("l%d_%s" % (i, kind), kind, shape)
                      for kind, shape in _layer_leaves(cfg, mixer)]
        specs.append(("norm_f", "norm_f", (cfg.d_model,)))
        self.param_names = [n for n, _, _ in specs]
        self._kinds = {n: k for n, k, _ in specs}
        self._shapes = {n: s for n, _, s in specs}
        self._specs = dict.fromkeys(self.param_names, P())
        if params is not None:
            for name, _, shape in specs:
                if tuple(params[name].shape) != shape:
                    raise ValueError("%s: shape %r, the program has %r"
                                     % (name, tuple(params[name].shape),
                                        shape))
        self._loaded = params

    # -- init -------------------------------------------------------------
    def _draw_leaf(self, key, kind, shape):
        """One leaf from ``key``, by its kind: projections normal over the
        root of their fan-in; the embedding normal times ``init_scale``;
        norms and ``D`` one; the convolution uniform within one over the
        root of its width, as ``torch.nn.Conv1d`` draws it; ``A_log`` the
        log of 1..heads and ``dt_bias`` the inverse softplus of a step
        log-uniform in [0.001, 0.1], as the ``mamba2`` modelling code
        sets them."""
        f32 = jnp.float32
        if kind.startswith("norm") or kind in ("ssm_norm", "ssm_d"):
            return jnp.ones(shape, f32)
        if kind == "embed":
            return jax.random.normal(key, shape, f32) * self.cfg.init_scale
        if kind in ("ssm_conv_w", "ssm_conv_b"):
            bound = self.cfg.ssm_conv ** -0.5
            return jax.random.uniform(key, shape, f32, -bound, bound)
        if kind == "ssm_a_log":
            return jnp.log(jnp.arange(1, shape[0] + 1, dtype=f32))
        if kind == "ssm_dt_bias":
            dt = jnp.exp(jax.random.uniform(
                key, shape, f32, math.log(1e-3), math.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        fan_in = shape[0] * shape[1] if kind == "wo" else shape[0]
        return jax.random.normal(key, shape, f32) / math.sqrt(fan_in)

    def init_params(self, seed=None):
        """name -> float32 array: the loaded weights where the block was
        given any, else every leaf drawn from the seed on the default
        device in one program."""
        if self._loaded is not None:
            return {n: self._loaded[n] for n in self.param_names}
        @jax.jit
        def draw(key):
            return {name: self._draw_leaf(jax.random.fold_in(key, i),
                                          self._kinds[name],
                                          self._shapes[name])
                    for i, name in enumerate(self.param_names)}

        return draw(jax.random.PRNGKey(
            self.cfg.init_seed if seed is None else int(seed)))

    # -- the per-replica forward + loss ------------------------------------
    def _attention(self, lp, x):
        cfg = self.cfg
        q, k, v = (checkpoint_name(
            jnp.einsum("btd,dhe->bthe", x, lp[w]), ssm.PROJECTION)
            for w in ("wq", "wk", "wv"))
        o = causal_gqa_attention(q, k, v, cfg.attention_multiplier,
                                 cfg.attention_block)
        return checkpoint_name(jnp.einsum("bthe,hed->btd", o, lp["wo"]),
                               ssm.PROJECTION)

    def _layer(self, mixer, lp, h):
        """One layer over its leaves ``lp`` (kind -> array)."""
        cfg = self.cfg
        scale = jnp.asarray(cfg.residual_multiplier, h.dtype)
        if mixer == "mamba":
            with jax.named_scope("mamba_mixer"):
                m = ssm.mamba2_mixer(
                    lp, rms_norm(h, lp["norm1"], cfg.norm_eps), cfg)
        else:
            with jax.named_scope("attention"):
                m = self._attention(
                    lp, rms_norm(h, lp["norm1"], cfg.norm_eps))
        h = h + scale * m
        with jax.named_scope("gated_mlp"):
            m = gated_mlp(lp, rms_norm(h, lp["norm2"], cfg.norm_eps))
        return h + scale * m

    def loss_replica(self, train_vals, x, y, key):
        """Mean token cross-entropy of the local ``(b, t)`` chunk over the
        held vocabulary; ``train_vals`` follow ``param_names``.  Every layer
        is one ``jax.checkpoint``; :func:`keeps_products` decides, while this
        is traced, whether the layers' projection products are among its
        residuals (docs/transformer.md "The layer table")."""
        cfg = self.cfg
        p = dict(zip(self.param_names, train_vals))
        dtype = p["embed"].dtype
        keep = keeps_products(cfg, sum(v.size for v in train_vals),
                              *x.shape, dtype, _device_bytes_limit())
        # one checkpoint a layer, with or without the tagged products among
        # its residuals; everything else of a layer is re-run either way
        policy = jax.checkpoint_policies.save_only_these_names(
            *([ssm.PROJECTION] if keep else []))
        with jax.named_scope("embed"):
            h = jnp.take(p["embed"], x, axis=0)
            h = h * jnp.asarray(cfg.embedding_multiplier, h.dtype)
        for i, mixer in enumerate(cfg.layer_types):
            lp = {kind: p["l%d_%s" % (i, kind)]
                  for kind in LAYER_LEAVES[mixer]}
            layer = jax.checkpoint(
                lambda lp, h, mixer=mixer: self._layer(mixer, lp, h),
                policy=policy)
            _compiles.count("recomputed_layers")
            _compiles.count("kept_product_layers", int(keep))
            if mixer == "mamba":
                _compiles.count("ssm_layers")
                _compiles.count("ssm_kernel_layers",
                                int(ssm.scan_kernel_tiles(cfg, dtype)))
            with jax.named_scope("l%d" % i):
                h = layer(lp, h)
        _compiles.note("ssm_chunks_per_seq",
                       -(-x.shape[1] // cfg.ssm_chunk))
        _compiles.note("kept_product_bytes",
                       kept_product_bytes(cfg, *x.shape, dtype) * keep)
        with jax.named_scope("lm_head_loss"):
            hf = rms_norm(h, p["norm_f"], cfg.norm_eps)
            logits = jnp.einsum("btd,vd->btv", hf, p["embed"],
                                preferred_element_type=jnp.float32)
            logits = logits / cfg.logits_scaling
            return L.vocab_parallel_cross_entropy(logits, y, self.plan).mean()

    def describe(self):
        return {"config": self.cfg.describe(), "plan": self.plan.describe(),
                "n_params": len(self.param_names)}
