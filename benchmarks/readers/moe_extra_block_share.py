"""The share of the timed window's layer-steps in which the expert loop
walked more than one block, a fraction of 1 (unit `x`): of every step and
MoE layer's `moe_rows_walked` (`facts["moe_rows_walked_by_step"]`), those
over the rows of one block of the program's own plan
(`alphafold2_tpu.ops.moe.block_rows_for` for the cell's tokens and the
configuration's picks and experts). A layer whose held load passes the
block pays for a second one whole, so this is the cliff a change of the
load or of the block moves. 0 where no layer-step passed a block; None
where the steps state no rows walked or the program has no block plan."""
import numpy as np


def block_rows(cfg, shape):
    """Rows of one block of the program's plan for the cell, or None."""
    width_key = getattr(cfg, "router_width_key", None)
    if shape is None or width_key is None:
        return None
    # a configuration with a router came from models/decoder.py, which
    # imports ops/moe.py itself
    from alphafold2_tpu.ops import moe

    if not hasattr(moe, "block_rows_for"):
        return None
    lo, hi = cfg.held
    return moe.block_rows_for(shape[0] * shape[1], cfg.num_experts_per_tok, hi - lo,
                              getattr(cfg, width_key))


def read(facts: dict, args: dict):
    rows = facts.get("moe_rows_walked_by_step")
    block = block_rows(facts.get("model_cfg"), facts.get("lm_shape"))
    if rows is None or block is None or not np.size(rows):
        return None
    return float(np.mean(np.asarray(rows) > block))
