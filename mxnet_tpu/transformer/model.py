"""Transformer LM over a 2-3D ``data × model × sequence`` mesh.

The model is spelled ONCE, per replica (the ``parallel/zero.py``
discipline): :meth:`MeshProgram.loss_replica` is a pure jax function
over LOCAL parameter shards and a LOCAL ``(B/Kd, T/Ks)`` token chunk,
with every cross-replica collective explicit.  The same function is

- jitted under ``shard_map`` by ``DataParallelTrainer(mesh_plan=...)``
  (the runtime), and
- traced with ``jax.make_jaxpr(axis_env=plan.axis_env())`` by
  ``trainer.mesh_report()`` and the ``tp_transformer_train_step``
  budget model (the hardware-free analysis),

so the executed program and the proven program can never drift.

Layer sharding (docs/transformer.md has the full table): token/output
embeddings vocab-parallel over ``model``; QKV column-parallel (heads
over ``model``); attention over the ``sequence`` axis via ring attention
(``parallel/ring_attention.py``) or Ulysses all-to-all when the local
head count divides; attention-out and MLP-down row-parallel with their
completing psum (the ``TP_ROW_PSUM`` seam); LayerNorms replicated.
Positions are global: each sequence rank offsets by
``axis_index("sequence") * T_local``.
"""
from __future__ import annotations

import numpy as _np

import jax

__all__ = ["TransformerLMConfig", "TransformerLM", "MeshProgram",
           "ProgramLayout"]


class TransformerLMConfig:
    """Pinned-geometry transformer-LM hyperparameters.

    ``attention`` picks the sequence-parallel kernel: ``"ring"`` (K/V
    chunks rotate over ``ppermute`` — any head count, O(T/K) memory),
    ``"ulysses"`` (two all-to-alls swap sequence for head sharding —
    needs ``(n_heads / model) % sequence == 0``) or ``"auto"`` (Ulysses
    when the head count divides, else ring — the decision rule in
    docs/transformer.md).  With a collapsed sequence axis all three are
    plain local causal attention.
    """

    def __init__(self, vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                 d_ff=64, seq_len=64, attention="ring", init_seed=0,
                 init_scale=0.02, microbatches=None):
        self.vocab_size = int(vocab_size)
        self.d_model = int(d_model)
        self.n_heads = int(n_heads)
        self.n_layers = int(n_layers)
        self.d_ff = int(d_ff)
        self.seq_len = int(seq_len)
        self.attention = str(attention)
        self.init_seed = int(init_seed)
        self.init_scale = float(init_scale)
        self.microbatches = (None if microbatches is None
                             else int(microbatches))
        if self.d_model % self.n_heads:
            raise ValueError("d_model %d must divide into n_heads %d"
                             % (self.d_model, self.n_heads))
        if self.attention not in ("ring", "ulysses", "auto"):
            raise ValueError("attention must be ring/ulysses/auto, got %r"
                             % (attention,))
        if self.microbatches is not None and self.microbatches < 1:
            raise ValueError("microbatches must be >= 1, got %r"
                             % (microbatches,))

    @property
    def head_dim(self):
        return self.d_model // self.n_heads

    def describe(self):
        return {k: getattr(self, k) for k in
                ("vocab_size", "d_model", "n_heads", "n_layers", "d_ff",
                 "seq_len", "attention", "init_seed", "microbatches")}


class TransformerLM:
    """The block handed to ``DataParallelTrainer(mesh_plan=...)`` — a
    thin config carrier implementing the mesh-program protocol the
    trainer's multi-axis tier consumes (``mesh_program(plan)``)."""

    def __init__(self, cfg):
        if not isinstance(cfg, TransformerLMConfig):
            cfg = TransformerLMConfig(**cfg)
        self.cfg = cfg

    def mesh_program(self, plan):
        return MeshProgram(self.cfg, plan)


def _attention_mode(cfg, plan):
    """The ring-vs-Ulysses decision rule (docs/transformer.md): Ulysses
    needs the LOCAL head count (heads already sharded over ``model``) to
    divide by the sequence-axis size; ``auto`` prefers it when legal
    (two all-to-alls move ~3x fewer bytes than a K-hop ring at moderate
    sequence lengths), ring otherwise."""
    if not plan.present("sequence"):
        return "local"
    h_local = cfg.n_heads // plan.size("model")
    divides = h_local % plan.size("sequence") == 0
    if cfg.attention == "ulysses":
        if not divides:
            raise ValueError(
                "ulysses attention needs local heads (%d) divisible by "
                "the sequence axis (%d); use attention='ring'"
                % (h_local, plan.size("sequence")))
        return "ulysses"
    if cfg.attention == "auto" and divides:
        return "ulysses"
    return "ring"


# one transformer block's parameter kinds, in declaration order — the
# per-layer (``l{i}_``) and stage-stacked (``blk_``) layouts both
# follow it, which is what keeps init_params' RNG draw order identical
# across plans (the bitwise same-seed contract)
_LAYER_KINDS = ("ln1_scale", "ln1_bias", "wq", "wk", "wv", "wo",
                "ln2_scale", "ln2_bias", "w1", "b1", "w2", "b2")

# parameters outside the block stack — pipe-replicated under
# ``pipeline=K``: only stage 0 (embeddings) / stage K-1 (final norm +
# head) produce nonzero gradients for them, completed by ONE psum over
# ``pipe`` (parallel/pipeline.py, reduce_replicated_grads)
_PIPE_REPLICATED = frozenset(
    ("embed", "pos_embed", "lnf_scale", "lnf_bias", "w_out"))


class ProgramLayout:
    """Where a mesh program's parameters live: what the trainer's mesh
    tier and ``transformer/step.py`` read of any program.  A subclass
    sets ``cfg``, ``plan``, ``_shapes`` (name -> global shape) and
    ``_specs`` (name -> PartitionSpec)."""

    def partition_spec(self, name):
        return self._specs[name]

    def global_shape(self, name):
        return self._shapes[name]

    def local_shape(self, name):
        """The per-replica shard shape — what the ``axis_env`` trace and
        the ``shard_map`` body see."""
        spec = self._specs[name]
        shape = list(self._shapes[name])
        for dim, entry in enumerate(spec):
            if entry is not None:
                shape[dim] //= self.plan.size(entry)
        return tuple(shape)

    def local_batch_shape(self, global_batch):
        b = global_batch // self.plan.size("data")
        t = self.cfg.seq_len // self.plan.size("sequence")
        return (b, t)


class MeshProgram(ProgramLayout):
    """One (config, plan) pair's concrete sharded program: parameter
    names/specs/local shapes, the deterministic global initializer, and
    the per-replica loss function (module docstring).

    With ``pipeline=K`` in the plan, the per-layer ``l{i}_*`` parameters
    are instead declared ONCE as stacked ``blk_*`` arrays with a leading
    ``(n_layers,)`` dim sharded over ``pipe`` — stage partitioning
    expressed through the exact same ``NamedSharding`` machinery as
    every other axis, so checkpoints/ZeRO/bf16 compose untouched — and
    :meth:`loss_replica` routes through the 1F1B schedule of
    ``parallel/pipeline.py`` (``M = cfg.microbatches`` or the stage
    count)."""

    def __init__(self, cfg, plan):
        from jax.sharding import PartitionSpec as P
        self.cfg = cfg
        self.plan = plan
        km, ks = plan.size("model"), plan.size("sequence")
        kp = plan.size("pipe")
        if cfg.n_heads % km:
            raise ValueError("n_heads %d must divide by the model axis %d"
                             % (cfg.n_heads, km))
        if cfg.d_ff % km:
            raise ValueError("d_ff %d must divide by the model axis %d"
                             % (cfg.d_ff, km))
        if cfg.vocab_size % km:
            raise ValueError("vocab_size %d must divide by the model "
                             "axis %d" % (cfg.vocab_size, km))
        if cfg.seq_len % max(ks, 1):
            raise ValueError("seq_len %d must divide by the sequence "
                             "axis %d" % (cfg.seq_len, ks))
        if cfg.n_layers % kp:
            raise ValueError("n_layers %d must divide by the pipeline "
                             "axis %d" % (cfg.n_layers, kp))
        self.attention_mode = _attention_mode(cfg, plan)
        self.pipelined = plan.present("pipe")
        self.n_micro = ((cfg.microbatches or kp)
                        if self.pipelined else None)
        self.pipe_replicated = (_PIPE_REPLICATED if self.pipelined
                                else frozenset())
        model = "model" if plan.present("model") else None
        d, h, e, f, v = (cfg.d_model, cfg.n_heads, cfg.head_dim,
                         cfg.d_ff, cfg.vocab_size)
        # kind -> (per-layer global shape, per-layer PartitionSpec
        # entries); axis names already collapsed (size-1 -> None)
        layer = [
            ("ln1_scale", (d,), (None,)),
            ("ln1_bias", (d,), (None,)),
            ("wq", (d, h, e), (None, model, None)),
            ("wk", (d, h, e), (None, model, None)),
            ("wv", (d, h, e), (None, model, None)),
            ("wo", (h, e, d), (model, None, None)),
            ("ln2_scale", (d,), (None,)),
            ("ln2_bias", (d,), (None,)),
            ("w1", (d, f), (None, model)),
            ("b1", (f,), (model,)),
            ("w2", (f, d), (model, None)),
            ("b2", (d,), (None,)),
        ]
        assert tuple(k for k, _, _ in layer) == _LAYER_KINDS
        # name -> (global shape, PartitionSpec) in parameter order
        specs = [("embed", (v, d), P(model, None)),
                 ("pos_embed", (cfg.seq_len, d), P())]
        if self.pipelined:
            specs += [("blk_" + kind, (cfg.n_layers,) + shape,
                       P("pipe", *entries))
                      for kind, shape, entries in layer]
        else:
            for i in range(cfg.n_layers):
                specs += [("l%d_%s" % (i, kind), shape, P(*entries))
                          for kind, shape, entries in layer]
        specs += [("lnf_scale", (d,), P()),
                  ("lnf_bias", (d,), P()),
                  ("w_out", (d, v), P(None, model))]
        self.param_names = [n for n, _, _ in specs]
        self._shapes = {n: s for n, s, _ in specs}
        self._specs = {n: p for n, _, p in specs}

    # -- init -------------------------------------------------------------
    @staticmethod
    def _init_leaf(rng, cfg, name, shape):
        """One per-layer-or-global leaf, by naming rule: scaled-normal
        weights, ones/zeros norms, zero biases.  ``shape`` is the
        PER-LAYER shape even in the stacked layout, so the RNG draws
        are identical across plans."""
        if name.endswith("_scale"):
            return _np.ones(shape, _np.float32)
        if name.endswith(("_bias", "b1", "b2")):
            return _np.zeros(shape, _np.float32)
        if name in ("embed", "pos_embed"):
            return (rng.randn(*shape) * cfg.init_scale
                    ).astype(_np.float32)
        # fan-in scaled: the contraction size of each matmul —
        # wo contracts (heads, head_dim), everything else dim 0
        fan_in = shape[0] * shape[1] if name.endswith("wo") \
            else shape[0]
        return (rng.randn(*shape) / _np.sqrt(max(fan_in, 1))
                ).astype(_np.float32)

    def init_params(self, seed=None):
        """Deterministic GLOBAL parameter arrays, name -> float32
        ndarray.  Same seed => bitwise-identical params at ANY plan (the
        numerics tests' baseline contract): the stacked ``blk_*`` layout
        draws each layer's leaves in the exact per-layer order of the
        replicated layout, then stacks — ``blk_wq[i]`` is bitwise
        ``l{i}_wq``."""
        cfg = self.cfg
        rng = _np.random.RandomState(
            cfg.init_seed if seed is None else int(seed))
        out = {}
        for name in ("embed", "pos_embed"):
            out[name] = self._init_leaf(rng, cfg, name, self._shapes[name])
        if self.pipelined:
            drawn = [{kind: self._init_leaf(
                rng, cfg, kind, self._shapes["blk_" + kind][1:])
                for kind in _LAYER_KINDS} for _ in range(cfg.n_layers)]
            for kind in _LAYER_KINDS:
                out["blk_" + kind] = _np.stack(
                    [drawn[i][kind] for i in range(cfg.n_layers)])
        else:
            for i in range(cfg.n_layers):
                for kind in _LAYER_KINDS:
                    name = "l%d_%s" % (i, kind)
                    out[name] = self._init_leaf(rng, cfg, kind,
                                                self._shapes[name])
        for name in ("lnf_scale", "lnf_bias", "w_out"):
            out[name] = self._init_leaf(rng, cfg, name, self._shapes[name])
        return out

    # -- the per-replica forward + loss ------------------------------------
    def _attend(self, q, k, v):
        from ..parallel.ring_attention import (local_attention,
                                               ring_attention,
                                               ulysses_attention)
        if self.attention_mode == "ring":
            return ring_attention(q, k, v, "sequence", causal=True)
        if self.attention_mode == "ulysses":
            return ulysses_attention(q, k, v, "sequence", causal=True)
        return local_attention(q, k, v, causal=True)

    def _embed_in(self, p, x):
        """Token + position embedding of a LOCAL ``(b, t)`` chunk onto
        the residual stream — the pipeline's stage-0 ingest."""
        from jax import lax

        from . import layers as L

        plan, t_local = self.plan, x.shape[1]
        h = L.vocab_parallel_embedding(p["embed"], x, plan)
        start = L.sequence_offset(plan, t_local)
        pos = lax.dynamic_slice(
            p["pos_embed"], (start, 0), (t_local, self.cfg.d_model))
        return h + pos[None].astype(h.dtype)

    def _block(self, lp, h):
        """One transformer block over per-layer param leaves ``lp``
        (kind -> local shard) — the same spelling whether the leaves
        come from ``l{i}_*`` names or a ``blk_*[j]`` stack slice."""
        import jax.numpy as jnp

        from . import layers as L

        plan = self.plan
        a = L.layer_norm(h, lp["ln1_scale"], lp["ln1_bias"])
        # Megatron f-op: every replicated activation entering a
        # column-parallel region needs its cotangent psum'd back
        a = L.copy_to_model(a, plan)
        q = jnp.einsum("btd,dhe->bthe", a, lp["wq"])
        k = jnp.einsum("btd,dhe->bthe", a, lp["wk"])
        v = jnp.einsum("btd,dhe->bthe", a, lp["wv"])
        o = self._attend(q, k, v)
        o = jnp.einsum("bthe,hed->btd", o, lp["wo"])
        h = h + L.row_parallel_out(o, plan)
        m = L.layer_norm(h, lp["ln2_scale"], lp["ln2_bias"])
        m = L.copy_to_model(m, plan)
        f = L.column_parallel_dense(m, lp["w1"], lp["b1"])
        f = jax.nn.gelu(f)
        f = f @ lp["w2"]
        return h + L.row_parallel_out(f, plan, bias=lp["b2"])

    def _head_loss(self, p, h, y):
        """Final norm + vocab-parallel head + mean token loss — the
        pipeline's last-stage scorer."""
        from . import layers as L

        plan = self.plan
        hf = L.layer_norm(h, p["lnf_scale"], p["lnf_bias"])
        hf = L.copy_to_model(hf, plan)
        logits = hf @ p["w_out"]
        return L.vocab_parallel_cross_entropy(logits, y, plan).mean()

    def loss_replica(self, train_vals, x, y, key):
        """Mean causal-LM loss of the LOCAL token chunk.  ``train_vals``
        follow ``param_names`` order (local shards); ``x``/``y`` are the
        local ``(B/Kd, T/Ks)`` int32 token/label chunks (labels already
        globally shifted by the feeder).  Collectives inside: the
        ``model``-axis psums of the sharded layers, the ``sequence``
        ring/all-to-all of attention and — under ``pipeline=K`` — the
        per-tick activation ``ppermute`` of the 1F1B schedule; NO
        data/sequence gradient reduction (the step wrapper owns that,
        exactly once: DST006)."""
        cfg, plan = self.cfg, self.plan
        p = dict(zip(self.param_names, train_vals))
        if self.pipelined:
            from ..parallel.pipeline import pipeline_loss

            layers_local = cfg.n_layers // plan.size("pipe")

            def stage_fn(h):
                for j in range(layers_local):
                    h = self._block(
                        {kind: p["blk_" + kind][j]
                         for kind in _LAYER_KINDS}, h)
                return h

            return pipeline_loss(
                lambda x_mb: self._embed_in(p, x_mb), stage_fn,
                lambda h, y_mb: self._head_loss(p, h, y_mb),
                x, y, plan, self.n_micro, act_dtype=p["embed"].dtype)
        h = self._embed_in(p, x)
        for i in range(cfg.n_layers):
            h = self._block({kind: p["l%d_%s" % (i, kind)]
                             for kind in _LAYER_KINDS}, h)
        return self._head_loss(p, h, y)

    def describe(self):
        out = {"config": self.cfg.describe(),
               "plan": self.plan.describe(),
               "attention_mode": self.attention_mode,
               "n_params": len(self.param_names)}
        if self.pipelined:
            out["pipeline"] = {"stages": self.plan.size("pipe"),
                               "microbatches": self.n_micro}
        return out
