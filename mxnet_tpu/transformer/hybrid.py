"""A language model whose layers come from a table, a pair a layer: a mixer
(Mamba-2 state-space, grouped-query attention, latent attention with rotary
positions, or delta-rule linear attention with a decay per channel) and a
feed-forward (gated, or sparse experts of which this chip holds a share), on
the ``mesh_plan`` path (docs/transformer.md "The layer table").

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

and those of the ``deepseek_v3`` family (``joyai_llm_flash``): no
multipliers, ``latent_attention`` mixers (``transformer/mla.py``), a leading
dense layer and ``sparse_experts`` after it (``transformer/moe.py``: told
``expert_shard = (index, of)``, the layer routes over all the experts and
computes the part its own give), an untied head, and ``mtp_modules`` 0 or 1
next-next-token module, whose loss is added with ``mtp_weight``::

    u_i = W_eh [RMSNorm_h(h_L,i) ; RMSNorm_e(E[y_i])]   h_L before its final norm
    u -> one more latent_attention + sparse_experts layer -> its own final
    RMSNorm -> the same head; target y_{i+1}; a row's last position left out
    loss = main + mtp_weight * module loss

and those of the ``bailing_hybrid`` family (``Ling-3.0-flash``): of every
``layer_group_size`` layers the last is ``latent_attention`` without the
low-rank query path (``q_lora_rank`` None: one product, no norm) and the
others ``linear_attention`` (``transformer/kda.py``), both with a head-wise
output gate (``attention_gate``); a router that chooses within the best
``topk_group`` of ``n_group`` groups of experts; no prediction module.

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

from ..ops import pallas_kernels
from ..telemetry import compiles as _compiles
from . import layers as L
from . import kda, mla, moe, ssm
from .layers import gated_mlp, rms_norm
from .model import ProgramLayout

__all__ = ["HybridLMConfig", "HybridLM", "HybridProgram",
           "causal_gqa_attention", "rms_norm", "gated_mlp",
           "kept_product_bytes", "keeps_products"]

MIXERS = ("mamba", "attention", "latent_attention", "linear_attention")
FEED_FORWARDS = ("gated_mlp", "sparse_experts")
# the model_types whose keys and equations are deepseek_v3's
DEEPSEEK_FAMILY = ("deepseek_v3", "joyai_llm_flash")
# what a training step holds on the mesh tier, bytes a parameter: float32
# master 4, compute copy 2, float32 gradient 4, float32 momentum 4
RESIDENT_BYTES_PER_PARAM = 14
# the share of the device's memory the reckoning of `keeps_products` may fill
ROOM = 0.9
# the `checkpoint_name` of what attention's backward pass reads besides its
# operands (the result and, from the flash kernels, the rows' logsumexp),
# which a layer's checkpoint always keeps: 1/24 of the products' bytes saves
# the layer's re-run attention's forward pass
ATTENTION_OUT = "attention_out"


class HybridLMConfig:
    """Sizes of a :class:`HybridLM`.  ``layer_types`` and ``ffn_types`` are
    the table: one mixer of :data:`MIXERS` and one feed-forward of
    :data:`FEED_FORWARDS` a layer (``ffn_types`` None: ``gated_mlp``
    throughout).  ``n_routed_experts`` is the router's width, the published
    count; ``expert_shard = (index, of)`` says which ``n_routed_experts /
    of`` of them are held here; the router chooses within the best
    ``topk_group`` of ``n_group`` equal groups of them (1 and 1: no group
    step).  ``q_lora_rank`` None is latent attention without the low-rank
    query path; ``attention_gate`` gives latent attention the head-wise
    output gate that ``linear_attention`` always has.  ``n_heads`` heads of
    ``kda_head_dim`` key and value columns, the convolution's width
    ``kda_conv``, the scan's ``kda_chunk`` and the gate's bound
    ``kda_lower_bound`` are the ``linear_attention`` mixer's
    (``transformer/kda.py``).  :meth:`from_hf` reads the keys of a published
    ``config.json`` by its ``model_type``."""

    def __init__(self, vocab_size=64, d_model=32, layer_types=("mamba",
                 "attention"), d_ff=64, n_heads=4, n_kv_heads=2, head_dim=8,
                 attention_multiplier=None, ssm_heads=4, ssm_head_dim=16,
                 ssm_state=8, ssm_conv=4, ssm_chunk=8, norm_eps=1e-5,
                 embedding_multiplier=1.0, residual_multiplier=1.0,
                 logits_scaling=1.0, seq_len=32, attention_block=512,
                 init_seed=0, init_scale=0.02, ffn_types=None,
                 tie_embeddings=True, q_lora_rank=16, kv_lora_rank=8,
                 qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8,
                 rope_theta=10000.0, moe_d_ff=16, n_routed_experts=8,
                 experts_per_token=2, expert_shard=(0, 1),
                 n_shared_experts=1, routed_scaling=1.0, norm_topk_prob=True,
                 mtp_modules=0, mtp_weight=0.3, n_group=1, topk_group=1,
                 attention_gate=False, kda_head_dim=8,
                 kda_conv=4, kda_chunk=8, kda_lower_bound=-5.0):
        self.vocab_size = int(vocab_size)
        self.d_model = int(d_model)
        self.layer_types = tuple(layer_types)
        self.ffn_types = (("gated_mlp",) * len(self.layer_types)
                          if ffn_types is None else tuple(ffn_types))
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
        self.tie_embeddings = bool(tie_embeddings)
        self.q_lora_rank = None if q_lora_rank is None else int(q_lora_rank)
        self.kv_lora_rank = int(kv_lora_rank)
        self.qk_nope_dim = int(qk_nope_dim)
        self.qk_rope_dim = int(qk_rope_dim)
        self.v_head_dim = int(v_head_dim)
        self.rope_theta = float(rope_theta)
        self.moe_d_ff = int(moe_d_ff)
        self.n_routed_experts = int(n_routed_experts)
        self.experts_per_token = int(experts_per_token)
        self.expert_shard = tuple(int(v) for v in expert_shard)
        self.n_shared_experts = int(n_shared_experts)
        self.routed_scaling = float(routed_scaling)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.mtp_modules = int(mtp_modules)
        self.mtp_weight = float(mtp_weight)
        self.n_group = int(n_group)
        self.topk_group = int(topk_group)
        self.attention_gate = bool(attention_gate)
        self.kda_head_dim = int(kda_head_dim)
        self.kda_conv = int(kda_conv)
        self.kda_chunk = int(kda_chunk)
        self.kda_lower_bound = float(kda_lower_bound)
        unknown = set(self.layer_types) - set(MIXERS)
        if unknown or not self.layer_types:
            raise ValueError("layer_types must name mixers of %s, got %r"
                             % (MIXERS, layer_types))
        if (len(self.ffn_types) != len(self.layer_types)
                or set(self.ffn_types) - set(FEED_FORWARDS)):
            raise ValueError(
                "ffn_types must name one feed-forward of %s a layer, got %r"
                % (FEED_FORWARDS, ffn_types))
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads %d must divide by n_kv_heads %d"
                             % (self.n_heads, self.n_kv_heads))
        index, of = self.expert_shard
        if self.n_routed_experts % of or not 0 <= index < of:
            raise ValueError(
                "expert_shard %r does not divide %d routed experts"
                % (self.expert_shard, self.n_routed_experts))
        groups = self.n_group
        if (self.n_routed_experts % groups
                or not 1 <= self.topk_group <= groups
                or self.experts_per_token
                > self.topk_group * (self.n_routed_experts // groups)):
            raise ValueError(
                "%d of %d groups of %d routed experts cannot give %d a token"
                % (self.topk_group, groups, self.n_routed_experts,
                   self.experts_per_token))
        if self.qk_rope_dim % 2:
            raise ValueError("qk_rope_dim %d is odd" % self.qk_rope_dim)
        if self.mtp_modules not in (0, 1):
            raise ValueError("mtp_modules is 0 or 1, got %d"
                             % self.mtp_modules)
        sub = min(kda.SUB_BLOCK, self.kda_chunk)
        if ("linear_attention" in self.layer_types
                and -self.kda_lower_bound * sub > kda.MAX_EXPONENT):
            raise ValueError(
                "kda_lower_bound %g lets a sub-block of %d tokens decay past "
                "e^-%g" % (self.kda_lower_bound, sub, kda.MAX_EXPONENT))

    @classmethod
    def from_hf(cls, config, **sizes):
        """From the keys of a published ``config.json``, by its
        ``model_type``: ``granitemoehybrid`` (dense), the ``deepseek_v3``
        family (``joyai_llm_flash``), or ``bailing_hybrid``
        (``Ling-3.0-flash``: ``layer_group_size``, the ``kda_*`` keys,
        ``n_group``/``topk_group``, a null ``q_lora_rank``).  ``sizes`` are
        the arguments the file does not hold (``seq_len``,
        ``attention_block``, ``expert_shard``, ``kda_chunk``, ...).  What is
        not implemented is refused by name, in the one form ``only <key>
        <value> is implemented, got <value>``."""
        family = config.get("model_type", "granitemoehybrid")
        if family == "granitemoehybrid":
            return cls._from_granite(config, **sizes)
        if family in DEEPSEEK_FAMILY:
            return cls._from_deepseek(config, **sizes)
        if family == "bailing_hybrid":
            return cls._from_bailing(config, **sizes)
        raise ValueError("model_type %r is not implemented" % family)

    @classmethod
    def _from_granite(cls, config, **sizes):
        """The first ``num_hidden_layers`` entries of ``layer_types`` are
        the layers."""
        if config.get("num_local_experts"):
            raise ValueError("granitemoehybrid's sparse experts "
                             "(num_local_experts) are not implemented")
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

    @classmethod
    def _from_deepseek(cls, config, **sizes):
        """``num_hidden_layers`` layers of latent attention, the first
        ``first_k_dense_replace`` with the dense feed-forward and the rest
        with sparse experts.  ``n_routed_experts`` is the router's width, as
        published; which of the experts are held here is no key of a
        ``config.json``: ``expert_shard=(index, of)`` among ``sizes`` says
        (absent: all of them).  ``n_group``/``topk_group`` above 1 give the
        router its group step, a null ``q_lora_rank`` the query path of one
        product."""
        for key, only in (("rope_scaling", None), ("scoring_func", "sigmoid"),
                          ("topk_method", "noaux_tc"), ("moe_layer_freq", 1),
                          ("hidden_act", "silu"), ("attention_bias", False),
                          ("rope_interleave", True)):
            if config.get(key, only) != only:
                raise ValueError("only %s %r is implemented, got %r"
                                 % (key, only, config[key]))
        modules = int(config.get("num_nextn_predict_layers", 0))
        if modules > 1:
            raise ValueError("only num_nextn_predict_layers 0 or 1 is "
                             "implemented, got %d" % modules)
        layers = int(config["num_hidden_layers"])
        dense = min(int(config["first_k_dense_replace"]), layers)
        return cls(
            vocab_size=config["vocab_size"], d_model=config["hidden_size"],
            layer_types=("latent_attention",) * layers,
            ffn_types=("gated_mlp",) * dense
            + ("sparse_experts",) * (layers - dense),
            d_ff=config["intermediate_size"],
            n_heads=config["num_attention_heads"],
            n_kv_heads=config["num_attention_heads"],
            q_lora_rank=config.get("q_lora_rank"),
            kv_lora_rank=config["kv_lora_rank"],
            qk_nope_dim=config["qk_nope_head_dim"],
            qk_rope_dim=config["qk_rope_head_dim"],
            v_head_dim=config["v_head_dim"],
            rope_theta=config["rope_theta"],
            n_group=config.get("n_group", 1),
            topk_group=config.get("topk_group", 1),
            moe_d_ff=config["moe_intermediate_size"],
            n_routed_experts=config["n_routed_experts"],
            experts_per_token=config["num_experts_per_tok"],
            n_shared_experts=config["n_shared_experts"],
            routed_scaling=config["routed_scaling_factor"],
            norm_topk_prob=config["norm_topk_prob"],
            tie_embeddings=config["tie_word_embeddings"],
            mtp_modules=modules,
            mtp_weight=config.get("mtp_loss_weight", 0.3),
            norm_eps=config["rms_norm_eps"], **sizes)

    @classmethod
    def _from_bailing(cls, config, **sizes):
        """``num_hidden_layers`` layers, of every ``layer_group_size`` the
        last with latent attention and the others with delta-rule linear
        attention; the first ``first_k_dense_replace`` with the dense
        feed-forward and the rest with sparse experts.  ``num_experts`` is
        the router's width, as published; ``expert_shard=(index, of)`` among
        ``sizes`` says which are held here (absent: all of them).  The five
        places where the family's keys admit two readings are fixed as
        docs/transformer.md "The layer table" says."""
        layers = int(config["num_hidden_layers"])
        for key, only in (
                ("use_kda_lora", False), ("no_kda_lora", True),
                ("kda_safe_gate", True), ("linear_silu", True),
                ("use_nGPT", False), ("value_norm", False),
                ("up_proj_norm", False), ("scale_router_input", False),
                ("score_function", "sigmoid"), ("scoring_func", "sigmoid"),
                ("topk_method", "noaux_tc"), ("rope_scaling", None),
                ("rope_interleave", True), ("hidden_act", "silu"),
                ("use_bias", False), ("use_qkv_bias", False),
                ("use_qk_norm", True), ("use_mla_nope", False),
                ("group_norm_size", 1),
                ("gated_attention_proj_granularity_type", "head_wise"),
                ("moe_router_enable_expert_bias", True),
                ("q_lora_rank", None), ("num_kv_heads_for_linear_attn", 0),
                ("num_nextn_predict_layers", 0),
                ("rotary_dim", config["qk_rope_head_dim"]),
                ("moe_shared_expert_intermediate_size",
                 config["moe_intermediate_size"])):
            if config.get(key, only) != only:
                raise ValueError("only %s %r is implemented, got %r"
                                 % (key, only, config[key]))
        # a clamp on the gated product of an expert or the shared expert
        for key in ("expert_swiglu_limit_list",
                    "share_expert_swiglu_limit_list"):
            held = list(config.get(key) or [])[:layers]
            if any(held):
                raise ValueError(
                    "only %s 0 in the layers held is implemented, got %r"
                    % (key, held))
        group = int(config["layer_group_size"])
        dense = min(int(config["first_k_dense_replace"]), layers)
        heads = int(config["num_attention_heads"])
        return cls(
            vocab_size=config["vocab_size"], d_model=config["hidden_size"],
            layer_types=["latent_attention" if (i + 1) % group == 0
                         else "linear_attention" for i in range(layers)],
            ffn_types=("gated_mlp",) * dense
            + ("sparse_experts",) * (layers - dense),
            d_ff=config["intermediate_size"], n_heads=heads,
            n_kv_heads=heads, q_lora_rank=None, attention_gate=True,
            kv_lora_rank=config["kv_lora_rank"],
            qk_nope_dim=config["qk_nope_head_dim"],
            qk_rope_dim=config["qk_rope_head_dim"],
            v_head_dim=config["v_head_dim"],
            rope_theta=config["rope_theta"],
            kda_head_dim=config["head_dim"],
            kda_conv=config["short_conv_kernel_size"],
            kda_lower_bound=config["kda_lower_bound"],
            moe_d_ff=config["moe_intermediate_size"],
            n_routed_experts=config["num_experts"],
            experts_per_token=config["num_experts_per_tok"],
            n_shared_experts=config["num_shared_experts"],
            routed_scaling=config["routed_scaling_factor"],
            norm_topk_prob=config["norm_topk_prob"],
            n_group=config["n_group"], topk_group=config["topk_group"],
            tie_embeddings=config["tie_word_embeddings"],
            norm_eps=config["rms_norm_eps"], **sizes)

    @property
    def ssm_inner(self):
        return self.ssm_heads * self.ssm_head_dim

    @property
    def experts_held(self):
        return self.n_routed_experts // self.expert_shard[1]

    @property
    def layers(self):
        """The table: ``(mixer, feed-forward)`` of every layer."""
        return tuple(zip(self.layer_types, self.ffn_types))

    @property
    def blocks(self):
        """``(prefix of the leaves' names, mixer, feed-forward)`` of every
        layer of the main model."""
        return tuple(("l%d_" % i,) + pair
                     for i, pair in enumerate(self.layers))

    @property
    def mtp_blocks(self):
        """The same of the prediction modules' layers."""
        return (("mtp_", "latent_attention", "sparse_experts"),
                ) * self.mtp_modules

    def describe(self):
        """The sizes that shape the program: the table, and the widths of
        the kinds it holds."""
        keys = ["vocab_size", "d_model", "layer_types", "ffn_types", "d_ff",
                "n_heads", "n_kv_heads", "head_dim", "ssm_heads",
                "ssm_head_dim", "ssm_state", "ssm_conv", "ssm_chunk",
                "seq_len", "init_seed", "tie_embeddings", "mtp_modules"]
        if "latent_attention" in self.layer_types:
            keys += ["q_lora_rank", "kv_lora_rank", "qk_nope_dim",
                     "qk_rope_dim", "v_head_dim", "rope_theta",
                     "attention_gate"]
        if "linear_attention" in self.layer_types:
            keys += ["kda_head_dim", "kda_conv", "kda_chunk",
                     "kda_lower_bound"]
        if "sparse_experts" in self.ffn_types:
            keys += ["moe_d_ff", "n_routed_experts", "experts_per_token",
                     "expert_shard", "experts_held", "n_shared_experts",
                     "routed_scaling", "n_group", "topk_group"]
        return {k: getattr(self, k) for k in keys}


def _layer_leaves(cfg, mixer, ffn="gated_mlp"):
    """[(kind, shape)] of one layer's leaves, in declaration order."""
    d, f = cfg.d_model, cfg.d_ff
    if mixer == "mamba":
        inner, n, h = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_heads
        mix = [("ssm_in", (d, 2 * inner + 2 * n + h)),
               ("ssm_conv_w", (cfg.ssm_conv, inner + 2 * n)),
               ("ssm_conv_b", (inner + 2 * n,)),
               ("ssm_dt_bias", (h,)), ("ssm_a_log", (h,)), ("ssm_d", (h,)),
               ("ssm_norm", (inner,)), ("ssm_out", (inner, d))]
    elif mixer == "latent_attention":
        mix = mla.leaves(cfg)
    elif mixer == "linear_attention":
        mix = kda.leaves(cfg)
    else:
        hq, hkv, e = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        mix = [("wq", (d, hq, e)), ("wk", (d, hkv, e)), ("wv", (d, hkv, e)),
               ("wo", (hq, e, d))]
    feed = (moe.leaves(cfg) if ffn == "sparse_experts"
            else [("mlp_in", (d, 2 * f)), ("mlp_out", (f, d))])
    return [("norm1", (d,))] + mix + [("norm2", (d,))] + feed


def _block_leaves(cfg, p, prefix, mixer, ffn):
    """kind -> array: one block's leaves out of ``p`` (name -> array); the
    kinds are the configuration's (docs/transformer.md has the table)."""
    return {kind: p[prefix + kind]
            for kind, _ in _layer_leaves(cfg, mixer, ffn)}


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


def _product_widths(cfg, mixer):
    """Widths of the tagged projection products of one mixer."""
    if mixer == "mamba":
        return [2 * cfg.ssm_inner + 2 * cfg.ssm_state + cfg.ssm_heads,
                cfg.d_model]
    if mixer == "latent_attention":
        return mla.product_widths(cfg)
    if mixer == "linear_attention":
        return kda.product_widths(cfg)
    return [cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim,
            cfg.n_kv_heads * cfg.head_dim, cfg.d_model]


def _product_elements(cfg, mixer, ffn, tokens):
    """Elements of one layer's tagged projection products over ``tokens``
    tokens: the mixer's and the feed-forward's a token, and for sparse
    experts the held experts' over the buffer of routed rows."""
    if ffn == "sparse_experts":
        feed = (tokens * 2 * cfg.n_shared_experts * cfg.moe_d_ff
                + moe.buffer_rows(cfg, tokens) * 2 * cfg.moe_d_ff)
    else:
        feed = tokens * 2 * cfg.d_ff
    return tokens * sum(_product_widths(cfg, mixer)) + feed


def kept_product_bytes(cfg, batch, seq, dtype):
    """Bytes of every layer's tagged projection products over a local
    chunk of ``batch x seq`` tokens in ``dtype``."""
    return sum(_product_elements(cfg, mixer, ffn, batch * seq)
               for _, mixer, ffn in cfg.blocks + cfg.mtp_blocks
               ) * jnp.dtype(dtype).itemsize


def _layer_live_bytes(cfg, mixer, batch, seq, dtype, ffn="gated_mlp"):
    """One layer's live set while its backward pass runs, reckoned from
    shapes: every intermediate of the layer once in ``dtype``, and four
    float32 arrays of the mixer's scores (the scan's decay matrix of every
    chunk and its masked product with ``C B^T``, or one block of query rows
    against the keys, of every sequence or in latent attention of one; each
    with its gradient).  A scan that runs as the kernel pair
    (``ssm.scan_kernel_tiles``) has no such array, nor has attention that
    runs as the flash kernels (:func:`attention_kernel_blocks`).  Sparse
    experts add what the buffer of routed rows holds (the rows gathered,
    the gate's halves and product, the rows' results in both types) and the
    float32 sum they are added into: the router's layout decides the rows,
    and both branches of its ``lax.cond`` work in a buffer of that size.
    Linear attention's live set is the chunked scan's, not a score matrix.
    Where the scan runs as the kernel pair (``kda.scan_kernel_tiles``) that
    is the state every chunk was handed, float32 ``E x E`` a chunk and head,
    and the five gradients the backward kernel writes (``dq``, ``dk``,
    ``dv`` in ``dtype``, ``dg`` in float32); in ``jax.numpy`` what one
    checkpointed block of ``kda.SCAN_BLOCK_CHUNKS`` chunks holds in float32
    a token and head (the running sums and their exponentials, the columns'
    factors of every sub-block, ``W``, ``U`` and the output; the ``L x L``
    system, the factors of its inverse and the scores), with their
    gradients."""
    d = cfg.d_model
    tokens = batch * seq
    if mixer == "linear_attention":
        inner, chunk = cfg.n_heads * cfg.kda_head_dim, cfg.kda_chunk
        # q, k, v from the convolution and normed, the gate in float32 (as
        # two), the output and its norm
        widths = 10 * inner
        if kda.scan_kernel_tiles(cfg, dtype):
            # the gradients of q, k, v and (as two) of the gate; the handed
            # states' 4 bytes an element as a quarter as many scores
            widths += 5 * inner
            scores = batch * -(-seq // chunk) * inner * cfg.kda_head_dim // 4
        else:
            block = min(seq, kda.SCAN_BLOCK_CHUNKS * chunk)
            subs = -(-chunk // kda.SUB_BLOCK)
            # float32 elements with their gradients, 8 bytes each: as half
            # as many of the 16 bytes the sum below takes a score at
            scores = batch * block * ((6 + subs) * inner
                                      + 12 * chunk * cfg.n_heads) // 2
    elif mixer == "mamba":
        inner, n = cfg.ssm_inner, cfg.ssm_state
        chunks = -(-seq // cfg.ssm_chunk)
        widths = 2 * (inner + 2 * n) + 3 * inner
        scores = 0 if ssm.scan_kernel_tiles(cfg, dtype) else \
            batch * chunks * cfg.ssm_heads * cfg.ssm_chunk ** 2
    else:
        scores = 0 if attention_kernel_blocks(cfg, mixer, seq, dtype) else \
            cfg.n_heads * min(cfg.attention_block, seq) * seq
        if mixer == "latent_attention":
            # q and k with their rotary parts turned, v, the output; a
            # block of query rows is of one sequence
            widths = cfg.n_heads * (2 * (cfg.qk_nope_dim + cfg.qk_rope_dim)
                                    + 2 * cfg.v_head_dim)
        else:
            widths = cfg.n_heads * cfg.head_dim
            scores *= batch
    elements = _product_elements(cfg, mixer, ffn, tokens) \
        + tokens * (widths + 4 * d)
    if ffn == "sparse_experts":
        elements += (tokens * (cfg.n_shared_experts * cfg.moe_d_ff + 2 * d)
                     + moe.buffer_rows(cfg, tokens)
                     * (4 * d + 3 * cfg.moe_d_ff))
    else:
        elements += tokens * cfg.d_ff
    return elements * jnp.dtype(dtype).itemsize + 4 * scores * 4


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
    live = max(_layer_live_bytes(cfg, mixer, batch, seq, dtype, ffn)
               for mixer, ffn in {b[1:] for b in cfg.blocks + cfg.mtp_blocks})
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


def attention_kernel_blocks(cfg, mixer, seq, dtype):
    """The blocks with which a ``mixer`` (``attention`` or
    ``latent_attention``) of ``cfg``'s sizes runs ``seq`` positions through
    the flash kernels, or None where :func:`causal_gqa_attention` spells it
    as blocks of rows (``pallas_kernels.flash_tiles`` on the mixer's heads
    and widths)."""
    if mixer == "latent_attention":
        return pallas_kernels.flash_tiles(
            seq, cfg.n_heads, cfg.n_heads, cfg.qk_nope_dim + cfg.qk_rope_dim,
            cfg.v_head_dim, dtype)
    return pallas_kernels.flash_tiles(seq, cfg.n_heads, cfg.n_kv_heads,
                                      cfg.head_dim, cfg.head_dim, dtype)


def causal_gqa_attention(q, k, v, scale, block):
    """Causal grouped-query attention.  q (b, t, heads, e); k (b, t,
    kv_heads, e); v (b, t, kv_heads, e or a width of its own), each
    key-value head serving ``heads / kv_heads`` query heads in order.

    Where ``pallas_kernels.flash_tiles`` takes the shapes (it is asked, and
    nothing else is): the flash kernels, forward and hand-written backward,
    so that no array of scores or probabilities reaches HBM in either
    direction.  The forward kernel runs outside what is differentiated; its
    result ``o`` and the rows' logsumexp carry the name
    :data:`ATTENTION_OUT`, and the backward kernels read them with ``q``,
    ``k`` and ``v``: a layer's ``jax.checkpoint`` that keeps that name
    re-runs the projections for the backward pass and not the kernel.

    Elsewhere (the rehearsal sizes, odd widths): ``block`` query rows at a
    time, so that no more than ``block x t`` scores a head are live and no
    key past a block's last row is read; the scores are recomputed in the
    backward pass by autodiff through a ``jax.checkpoint`` a block, which
    keeps the block's prefix of the keys and values.  ``o`` carries the name
    there too.  Both multiply operands of the arrays' dtype into float32,
    take the mask, the maximum, the exponential and the sums in float32, and
    cast the probabilities to the values' dtype for the second product."""
    b, t, heads, e = q.shape
    kv = k.shape[2]
    kept = lambda a: checkpoint_name(a, ATTENTION_OUT)
    blocks = pallas_kernels.flash_tiles(t, heads, kv, e, v.shape[-1],
                                        q.dtype)
    if blocks:
        # the kernels' layout is heads before time; XLA gives the
        # projections' results that layout where they are made
        heads_first = lambda a: a.transpose(0, 2, 1, 3)
        return heads_first(pallas_kernels.flash_mha(
            *map(heads_first, (q, k, v)), True, scale, blocks, kept=kept))
    q = q.reshape(b, t, kv, heads // kv, e)
    rows = jax.checkpoint(_attend_rows, static_argnums=(3, 4))
    out = [rows(q[:, start:start + block], k[:, :start + block],
                v[:, :start + block], scale, start)
           for start in range(0, t, block)]
    return kept(jnp.concatenate(out, axis=1).reshape(b, t, heads,
                                                     v.shape[-1]))


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
        d = cfg.d_model

        def block(prefix, mixer, ffn):
            return [(prefix + kind, kind, shape)
                    for kind, shape in _layer_leaves(cfg, mixer, ffn)]

        specs = [("embed", "embed", (cfg.vocab_size, d))]
        for entry in cfg.blocks:
            specs += block(*entry)
        specs.append(("norm_f", "norm_f", (d,)))
        if not cfg.tie_embeddings:
            specs.append(("head", "head", (cfg.vocab_size, d)))
        for entry in cfg.mtp_blocks:
            specs += [("mtp_norm_h", "norm_h", (d,)),
                      ("mtp_norm_e", "norm_e", (d,)),
                      ("mtp_eh_proj", "eh_proj", (2 * d, d))]
            specs += block(*entry) + [("mtp_norm_f", "norm_f", (d,))]
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
        """One leaf from ``key``, by its kind (``kda_a_log`` and
        ``kda_dt_bias`` as the code below says): projections normal over the
        root of their fan-in; the embedding and an untied head normal times
        ``init_scale``; norms and ``D`` one; the router's choosing bias
        uniform within 0.1 (so that choosing by ``s + b`` and weighing by
        ``s`` differ); the convolution uniform within one over the
        root of its width, as ``torch.nn.Conv1d`` draws it; ``A_log`` the
        log of 1..heads and ``dt_bias`` the inverse softplus of a step
        log-uniform in [0.001, 0.1], as the ``mamba2`` modelling code
        sets them."""
        f32 = jnp.float32
        if kind.startswith("norm") or kind in ("ssm_norm", "ssm_d",
                                               "kda_norm"):
            return jnp.ones(shape, f32)
        if kind == "kda_a_log":
            # rates within a factor of two of one, and biases from -8 to 2,
            # spread the gate over (lower_bound, 0): memories from a fifth
            # of a token to a thousand
            return jax.random.uniform(key, shape, f32, -math.log(2.0),
                                      math.log(2.0))
        if kind == "kda_dt_bias":
            return jax.random.uniform(key, shape, f32, -8.0, 2.0)
        if kind.startswith("kda_conv_"):
            bound = self.cfg.kda_conv ** -0.5
            return jax.random.uniform(key, shape, f32, -bound, bound)
        if kind in ("embed", "head"):
            return jax.random.normal(key, shape, f32) * self.cfg.init_scale
        if kind == "router_bias":
            return jax.random.uniform(key, shape, f32, -0.1, 0.1)
        if kind in ("ssm_conv_w", "ssm_conv_b"):
            bound = self.cfg.ssm_conv ** -0.5
            return jax.random.uniform(key, shape, f32, -bound, bound)
        if kind == "ssm_a_log":
            return jnp.log(jnp.arange(1, shape[0] + 1, dtype=f32))
        if kind == "ssm_dt_bias":
            dt = jnp.exp(jax.random.uniform(
                key, shape, f32, math.log(1e-3), math.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        fan_in = {"wo": shape[0] * shape[1], "router": shape[-1],
                  "moe_in": shape[1], "moe_out": shape[1]}.get(kind, shape[0])
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

    def _latent_attention(self, lp, x):
        cfg = self.cfg
        q, k, v = mla.queries_keys_values(lp, x, cfg)
        o = causal_gqa_attention(q, k, v, q.shape[-1] ** -0.5,
                                 cfg.attention_block)
        if cfg.attention_gate:
            with jax.named_scope("mla_gate"):
                o = mla.gated(lp, x, o)
        with jax.named_scope("mla_out_proj"):
            return checkpoint_name(
                jnp.einsum("bthe,hed->btd", o, lp["wo"]), ssm.PROJECTION)

    def _mix(self, mixer, lp, h):
        """A layer's first half: the residual stream after its mixer."""
        cfg = self.cfg
        scale = jnp.asarray(cfg.residual_multiplier, h.dtype)
        if mixer == "mamba":
            with jax.named_scope("mamba_mixer"):
                m = ssm.mamba2_mixer(
                    lp, rms_norm(h, lp["norm1"], cfg.norm_eps), cfg)
        elif mixer == "linear_attention":
            with jax.named_scope("kda_mixer"):
                m = kda.kda_mixer(
                    lp, rms_norm(h, lp["norm1"], cfg.norm_eps), cfg)
        else:
            attend = (self._latent_attention if mixer == "latent_attention"
                      else self._attention)
            with jax.named_scope("attention"):
                m = attend(lp, rms_norm(h, lp["norm1"], cfg.norm_eps))
        return h + scale * m

    def _feed(self, ffn, lp, h):
        """A layer's second half: the stream after its feed-forward."""
        cfg = self.cfg
        scale = jnp.asarray(cfg.residual_multiplier, h.dtype)
        if ffn == "sparse_experts":
            with jax.named_scope("sparse_experts"):
                m = moe.sparse_experts(
                    lp, rms_norm(h, lp["norm2"], cfg.norm_eps), cfg)
        else:
            with jax.named_scope("gated_mlp"):
                m = gated_mlp(lp, rms_norm(h, lp["norm2"], cfg.norm_eps))
        return h + scale * m

    def _layer(self, mixer, ffn, lp, h):
        """One layer over its leaves ``lp`` (kind -> array)."""
        return self._feed(ffn, lp, self._mix(mixer, lp, h))

    def _embed(self, p, ids):
        with jax.named_scope("embed"):
            h = jnp.take(p["embed"], ids, axis=0)
            return h * jnp.asarray(self.cfg.embedding_multiplier, h.dtype)

    def _token_losses(self, p, h, norm, y):
        """Cross-entropy of every position of ``h`` (b, t, d) against ``y``
        over the rows held, through the final norm ``norm`` and the head
        (the embedding where it is tied), float32 (b, t).  Where a
        prediction module makes the heads two, each is a
        ``jax.checkpoint``: the backward pass re-runs its logits, and the
        step never holds both heads' (1 GB each at 16k tokens over 16k
        rows)."""
        cfg = self.cfg

        def losses(h, weight, table):
            hf = rms_norm(h, weight, cfg.norm_eps)
            logits = jnp.einsum("btd,vd->btv", hf, table,
                                preferred_element_type=jnp.float32)
            logits = logits / cfg.logits_scaling
            return L.vocab_parallel_cross_entropy(logits, y, self.plan)

        if cfg.mtp_modules:
            losses = jax.checkpoint(losses)
        with jax.named_scope("lm_head_loss"):
            return losses(h, p[norm],
                          p["embed" if cfg.tie_embeddings else "head"])

    def loss_replica(self, train_vals, x, y, key):
        """Mean token cross-entropy of the local ``(b, t)`` chunk over the
        held vocabulary, and ``mtp_weight`` times the prediction module's
        where the configuration has one; ``train_vals`` follow
        ``param_names``.  Every layer is one ``jax.checkpoint``;
        :func:`keeps_products` decides, while this is traced, whether the
        layers' projection products are among its residuals
        (docs/transformer.md "The layer table")."""
        cfg = self.cfg
        p = dict(zip(self.param_names, train_vals))
        dtype = p["embed"].dtype
        keep = keeps_products(cfg, sum(v.size for v in train_vals),
                              *x.shape, dtype, _device_bytes_limit())
        # one checkpoint a layer, with or without the tagged products among
        # its residuals; everything else of a layer is re-run either way
        policy = jax.checkpoint_policies.save_only_these_names(
            ATTENTION_OUT, *([ssm.PROJECTION] if keep else []))

        def run(prefix, mixer, ffn, h):
            lp = _block_leaves(cfg, p, prefix, mixer, ffn)
            layer = jax.checkpoint(
                lambda lp, h: self._layer(mixer, ffn, lp, h), policy=policy)
            _compiles.count("recomputed_layers")
            _compiles.count("kept_product_layers", int(keep))
            if mixer == "mamba":
                _compiles.count("ssm_layers")
                _compiles.count("ssm_kernel_layers",
                                int(ssm.scan_kernel_tiles(cfg, dtype)))
            elif mixer == "linear_attention":
                _compiles.count("linear_attention_layers")
                _compiles.count("kda_kernel_layers",
                                int(kda.scan_kernel_tiles(cfg, dtype)))
            else:
                _compiles.count("attention_layers")
                _compiles.count("flash_attention_layers", int(bool(
                    attention_kernel_blocks(cfg, mixer, x.shape[1], dtype))))
            _compiles.count("latent_attention_layers",
                            int(mixer == "latent_attention"))
            _compiles.count("moe_layers", int(ffn == "sparse_experts"))
            return layer(lp, h)

        h = self._embed(p, x)
        for i, entry in enumerate(cfg.blocks):
            with jax.named_scope("l%d" % i):
                h = run(*entry, h)
        _compiles.note("ssm_chunks_per_seq",
                       -(-x.shape[1] // cfg.ssm_chunk))
        _compiles.note("kept_product_bytes",
                       kept_product_bytes(cfg, *x.shape, dtype) * keep)
        if "linear_attention" in cfg.layer_types:
            _compiles.note("kda_chunks_per_seq",
                           -(-x.shape[1] // cfg.kda_chunk))
        if "sparse_experts" in cfg.ffn_types:
            _compiles.note("moe_groups_kept",
                           cfg.topk_group if cfg.n_group > 1 else 0)
            _compiles.note("experts_held", cfg.experts_held)
            _compiles.note("router_width", cfg.n_routed_experts)
            _compiles.note("moe_grouped_rows", moe.buffer_rows(cfg, x.size))
            _compiles.note("moe_expected_rows",
                           int(moe.expected_rows(cfg, x.size)))
        loss = self._token_losses(p, h, "norm_f", y).mean()
        for entry in cfg.mtp_blocks:
            _compiles.count("mtp_modules")
            with jax.named_scope("mtp_module"):
                # position i reads the stream before the final norm and the
                # token after it, and is asked for the one after that
                u = jnp.concatenate(
                    [rms_norm(h, p["mtp_norm_h"], cfg.norm_eps),
                     rms_norm(self._embed(p, y), p["mtp_norm_e"],
                              cfg.norm_eps)], axis=-1) @ p["mtp_eh_proj"]
                u = run(*entry, u)
                # a row's last position has no token two ahead
                ahead = jnp.pad(y[:, 1:], ((0, 0), (0, 1)))
                asked = (jnp.arange(y.shape[1]) < y.shape[1] - 1).astype(
                    jnp.float32)
                losses = self._token_losses(p, u, "mtp_norm_f", ahead)
                loss = loss + cfg.mtp_weight * (
                    jnp.sum(losses * asked) / (y.shape[0] * asked.sum()))
        return loss

    def routing_report(self, train_vals, x):
        """Where the routers of the main model's expert layers send the
        tokens ``x`` (b, t) under the weights ``train_vals``: for every such
        layer the rows routed here, against the buffer's and an even
        router's, and the load of each expert held with its largest over its
        mean.  A host-side diagnostic outside the step
        (docs/observability.md "Inside the step")."""
        cfg = self.cfg

        @jax.jit
        def loads(train_vals, x):
            p = dict(zip(self.param_names, train_vals))
            h, out = self._embed(p, x), []
            for prefix, mixer, ffn in cfg.blocks:
                lp = _block_leaves(cfg, p, prefix, mixer, ffn)
                h = self._mix(mixer, lp, h)
                if ffn == "sparse_experts":
                    out.append(moe.held_loads(
                        lp, rms_norm(h, lp["norm2"], cfg.norm_eps), cfg))
                h = self._feed(ffn, lp, h)
            return out

        names = [prefix[:-1] for prefix, _, ffn in cfg.blocks
                 if ffn == "sparse_experts"]
        report = []
        for name, load in zip(names, jax.device_get(
                loads(tuple(train_vals), x))):
            load = [int(v) for v in load]
            mean = sum(load) / len(load)
            report.append({
                "layer": name, "rows": sum(load),
                "buffer_rows": moe.buffer_rows(cfg, x.size),
                "expected_rows": moe.expected_rows(cfg, x.size),
                "load": load,
                "max_over_mean": max(load) / mean if mean else 0.0})
        return report

    def describe(self):
        return {"config": self.cfg.describe(), "plan": self.plan.describe(),
                "n_params": len(self.param_names)}
