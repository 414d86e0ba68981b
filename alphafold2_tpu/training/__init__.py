"""Training harness layer.

Replaces the reference's inlined script loops and empty launcher stubs
(reference train_pre.py, train_end2end.py, training_scripts/) with a
first-class subsystem: losses, an optax-based jitted train step with
scanned gradient accumulation, and a static-shape data pipeline.
"""

from alphafold2_tpu.training.losses import (
    IGNORE_INDEX,
    bucketed_distance_matrix,
    distogram_cross_entropy,
)
from alphafold2_tpu.training.harness import (
    add_train_args,
    tcfg_from_args,
    TrainConfig,
    distogram_loss_fn,
    make_optimizer,
    make_train_step,
    train_state_init,
    with_fault_injection,
)
from alphafold2_tpu.training.data import (
    DataConfig,
    ResilientBatches,
    assemble_global_batch,
    bucket_batches,
    bucketed_microbatches,
    per_process_microbatch_fn,
    process_shard,
    resilient_batches,
    shard_items,
    stack_microbatches,
    synthetic_batches,
    synthetic_microbatch_fn,
    synthetic_structure_batches,
    sidechainnet_batches,
    sidechainnet_structure_batches,
)
from alphafold2_tpu.training.e2e import (
    E2EConfig,
    e2e_loss_fn,
    make_e2e_loss_fn,
    e2e_train_state_init,
    predict_structure,
)
from alphafold2_tpu.training.lm import (
    lm_aux_update,
    lm_loss_fn,
    lm_params_init,
    lm_train_state_init,
    zipf_token_batches,
)
from alphafold2_tpu.training.presets import (
    north_star_e2e_config,
)
from alphafold2_tpu.training.segmented import (
    make_segmented_train_step,
)
from alphafold2_tpu.training.checkpoint import (
    CheckpointManager,
    VerifiedCheckpointManager,
    abstract_like,
    finish,
    open_or_init,
    restore_or_init,
    restore_params_for_inference,
)
from alphafold2_tpu.training.resilience import (
    BadStepError,
    StepGuard,
    add_resilience_args,
    chaos_from_args,
    resilient_mode,
    run_resilient,
)

__all__ = [
    "add_train_args",
    "tcfg_from_args",
    "BadStepError",
    "StepGuard",
    "add_resilience_args",
    "chaos_from_args",
    "resilient_mode",
    "run_resilient",
    "with_fault_injection",
    "CheckpointManager",
    "VerifiedCheckpointManager",
    "abstract_like",
    "finish",
    "open_or_init",
    "restore_or_init",
    "restore_params_for_inference",
    "E2EConfig",
    "e2e_loss_fn",
    "make_e2e_loss_fn",
    "e2e_train_state_init",
    "predict_structure",
    "synthetic_structure_batches",
    "IGNORE_INDEX",
    "bucketed_distance_matrix",
    "distogram_cross_entropy",
    "TrainConfig",
    "distogram_loss_fn",
    "make_optimizer",
    "make_train_step",
    "train_state_init",
    "DataConfig",
    "ResilientBatches",
    "bucket_batches",
    "bucketed_microbatches",
    "assemble_global_batch",
    "per_process_microbatch_fn",
    "process_shard",
    "resilient_batches",
    "shard_items",
    "stack_microbatches",
    "synthetic_batches",
    "synthetic_microbatch_fn",
    "sidechainnet_batches",
    "sidechainnet_structure_batches",
    "north_star_e2e_config",
    "make_segmented_train_step",
    "lm_aux_update",
    "lm_loss_fn",
    "lm_params_init",
    "lm_train_state_init",
    "zipf_token_batches",
]
