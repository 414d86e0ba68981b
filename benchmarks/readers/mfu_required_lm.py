"""The decoder's whole training step as a share of the chip's bf16 peak
on REQUIRED operations: 3 x forward (`flops_lm.py`: the causal half of the
logits, the expected assignments held, what `jax.checkpoint` computes
again not counted) / train_step_s / peak."""
import common
import flops_lm


def read(facts: dict, args: dict):
    if "train_step_s" not in facts or "lm_shape" not in facts:
        return None
    need = flops_lm.decoder_required_train_flops(facts["model_cfg"], *facts["lm_shape"])
    peak = common.peaks_for(facts["device_kind"])["bf16_flops"]
    return 100.0 * need / facts["train_step_s"] / peak
