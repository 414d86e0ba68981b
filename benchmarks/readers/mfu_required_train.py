"""The whole training step's share of the chip's bf16 peak on REQUIRED
operations: 3 x forward (the reversible trunk's recompute is not
counted) / train_step_s / peak."""
import common
import flops


def read(facts: dict, args: dict):
    if "train_step_s" not in facts:
        return None
    n, r, c = facts["grid"]
    need = flops.required_train_flops(facts["model_cfg"], n, r, c)
    peak = common.peaks_for(facts["device_kind"])["bf16_flops"]
    return 100.0 * need / facts["train_step_s"] / peak
