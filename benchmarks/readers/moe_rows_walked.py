"""Sorted rows the expert layer walks a MoE layer over the assignments it
holds there, a ratio (unit `x`, at least 1: the rows beyond the load are
gathered, masked and scattered like the live ones): the program's own plan
for the cell's tokens and the configuration's picks and experts, over
`facts["assignments_held"]` (the router's own count, the mean of the
counted steps' layers; the plan is asked at that mean). A tree with the
loop over live blocks states its plan as `ops.moe.rows_walked` of
`block_rows_for`; one from before it walked whole chunks of
`chunk_rows_for` rows, a chunk past the load skipped: its live chunks
times a chunk's rows. None where the configuration has no expert layer or
the program neither plan."""
import math


def read(facts: dict, args: dict):
    cfg, shape = facts.get("model_cfg"), facts.get("lm_shape")
    held = facts.get("assignments_held")
    width_key = getattr(cfg, "router_width_key", None)
    if shape is None or not held or width_key is None:
        return None
    lo, hi = cfg.held
    plan = (shape[0] * shape[1], cfg.num_experts_per_tok, hi - lo,
            getattr(cfg, width_key))
    # a configuration with a router came from models/decoder.py, which
    # imports ops/moe.py itself
    from alphafold2_tpu.ops import moe

    if hasattr(moe, "rows_walked"):
        return float(moe.rows_walked(held, moe.block_rows_for(*plan))) / held
    if hasattr(moe, "chunk_rows_for"):
        chunk = moe.chunk_rows_for(*plan)
        return math.ceil(held / chunk) * chunk / held
    return None
