"""A decoder's whole training step as a share of the chip's bf16 peak on
REQUIRED operations, counted by the benchmark's file that `args["module"]`
names (`flops_zaya`; it offers `decoder_required_train_flops(cfg, batch,
length)` as `flops_lm.py` does): 3 x forward (the causal half of the
logits, the expected assignments held, what `jax.checkpoint` computes
again not counted) / train_step_s / peak. What `mfu_required_lm` reads
for the one module it imports."""
import importlib

import common


def read(facts: dict, args: dict):
    if "train_step_s" not in facts or "lm_shape" not in facts:
        return None
    counts = importlib.import_module(args["module"])
    need = counts.decoder_required_train_flops(facts["model_cfg"], *facts["lm_shape"])
    peak = common.peaks_for(facts["device_kind"])["bf16_flops"]
    return 100.0 * need / facts["train_step_s"] / peak
