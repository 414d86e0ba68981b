"""Model layer: the dual-track (pair representation + MSA) attention trunk
and the Alphafold2 model (reference alphafold2_pytorch/alphafold2.py:290-545),
re-designed as pure init/apply functions over param pytrees.
"""

from alphafold2_tpu.models.alphafold2 import (
    Alphafold2Config,
    alphafold2_init,
    alphafold2_apply,
    alphafold2_front,
    alphafold2_head,
)
from alphafold2_tpu.models.convert import convert_alphafold2
from alphafold2_tpu.models.decoder import (
    DecoderConfig,
    MellumConfig,
    OuroConfig,
    ZayaConfig,
    decoder_apply,
    decoder_init,
)
from alphafold2_tpu.models.trunk import (
    trunk_layer_init,
    sequential_trunk_apply,
)
from alphafold2_tpu.models.reversible import (
    reversible_trunk_init,
    reversible_trunk_apply,
    stack_layers,
)
from alphafold2_tpu.models.refiner import (
    RefinerConfig,
    refiner_init,
    refiner_apply,
)
from alphafold2_tpu.models.embedder import (
    EmbedderConfig,
    convert_esm_state_dict,
    convert_hf_esm_state_dict,
    embed_sequences,
    embedder_apply,
    embedder_init,
    esm_tokenize,
)

__all__ = [
    "DecoderConfig",
    "MellumConfig",
    "OuroConfig",
    "ZayaConfig",
    "decoder_apply",
    "decoder_init",
    "EmbedderConfig",
    "convert_esm_state_dict",
    "convert_hf_esm_state_dict",
    "embed_sequences",
    "embedder_apply",
    "embedder_init",
    "esm_tokenize",
    "RefinerConfig",
    "refiner_init",
    "refiner_apply",
    "Alphafold2Config",
    "alphafold2_init",
    "alphafold2_apply",
    "alphafold2_front",
    "alphafold2_head",
    "trunk_layer_init",
    "sequential_trunk_apply",
    "reversible_trunk_init",
    "reversible_trunk_apply",
    "stack_layers",
    "convert_alphafold2",
]
