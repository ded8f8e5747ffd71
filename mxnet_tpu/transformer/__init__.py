"""mxnet_tpu.transformer — the 2-3D-mesh tensor/sequence-parallel tier.

A transformer LM trained end-to-end over a ``data × model × sequence``
:class:`~mxnet_tpu.parallel.mesh.MeshPlan` (docs/transformer.md):
Megatron-style column/row-sharded dense + vocab-parallel embeddings and
loss over ``model`` (arxiv 1810.09868's whole-program annotations,
spelled per replica), ring or Ulysses attention over ``sequence``
(``parallel/ring_attention.py`` — now trained with, not just shipped),
composing with the ZeRO-1 sharded optimizer of ``parallel/zero.py``
(arxiv 2004.13336) on the ``data`` axis.

Entry points::

    cfg = TransformerLMConfig(vocab_size=256, d_model=128, n_heads=8,
                              n_layers=4, d_ff=512, seq_len=1024)
    trainer = DataParallelTrainer(
        TransformerLM(cfg), None, "sgd", {"learning_rate": 0.1},
        mesh_plan=MeshPlan(data=2, model=2, sequence=2), zero=1)
    trainer.step(tokens, labels)          # (B, T) int32 global batches

The step is proven hardware-free by the ``tp_transformer_train_step``
budget model (STATIC_BUDGETS.json) whose runtime tape must match the
fixture — see ``analysis/budget_models.py`` and
``trainer.mesh_report()``.
"""
from .model import TransformerLM, TransformerLMConfig, MeshProgram
from .hybrid import HybridLM, HybridLMConfig, HybridProgram
from . import layers, mla, moe, ssm, step

__all__ = ["TransformerLM", "TransformerLMConfig", "MeshProgram",
           "HybridLM", "HybridLMConfig", "HybridProgram",
           "layers", "mla", "moe", "ssm", "step"]
