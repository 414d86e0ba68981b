"""Ops layer: functional NN primitives, attention (dense / axial / tied-row /
KV-compressed / block-sparse), and feed-forward blocks.

Everything here is a pure function over explicit parameter pytrees — the
TPU-native answer to the reference's `torch.nn.Module` ops layer
(reference alphafold2_pytorch/alphafold2.py:30-286).

Hot ops (flash/fused attention, quant matmul, sparse attention, the
ring hop, the experts' grouped product) resolve their backend arm —
pallas_tpu / xla_ref — through ONE registry, `ops/dispatch.py`
(`resolve`), with every AF2_* env knob defined once in `ops/knobs.py`.
"""

from alphafold2_tpu.ops.core import (
    linear_init,
    linear,
    layer_norm_init,
    layer_norm,
    embedding_init,
    embedding,
    dropout,
)
from alphafold2_tpu.ops.attention import (
    AttentionConfig,
    attention_init,
    attention_apply,
    axial_attention_init,
    axial_attention_apply,
)
from alphafold2_tpu.ops.feedforward import (
    feed_forward_init,
    feed_forward_apply,
)
from alphafold2_tpu.ops.dispatch import (
    resolution_table,
    resolution_tag,
    resolve,
)
from alphafold2_tpu.ops.flash import blockwise_attention, flash_attention
from alphafold2_tpu.ops.quant import (
    dequantize_tree,
    dequantize_weight,
    quant_matmul,
    quantize_tree,
    quantize_weight,
    reject_quant_training,
    tree_weight_bytes,
)

__all__ = [
    "resolution_table",
    "resolution_tag",
    "resolve",
    "dequantize_tree",
    "dequantize_weight",
    "quant_matmul",
    "quantize_tree",
    "quantize_weight",
    "reject_quant_training",
    "tree_weight_bytes",
    "linear_init",
    "linear",
    "layer_norm_init",
    "layer_norm",
    "embedding_init",
    "embedding",
    "dropout",
    "AttentionConfig",
    "attention_init",
    "attention_apply",
    "axial_attention_init",
    "axial_attention_apply",
    "feed_forward_init",
    "feed_forward_apply",
    "blockwise_attention",
    "flash_attention",
]
