"""A kernel's share of its roofline over the traced steps, in percent:
the least time the chip could take for the work ASKED of the scope in one
step (the larger of required FLOPs / bf16 peak and required bytes / HBM
peak, both from `flops_lm.py`, whatever arm or kernel does the work) over
the scope's device self seconds a step (`scope_reduce.reduce_scopes`,
kept as `facts["scopes"]`). `args`: `scope` (a key of the table) and
`work` (`attn_core` or `experts`, naming `flops_lm.<work>_train_flops` /
`_bytes`). The experts' count takes the assignments the router really
gave (`facts["assignments_held"]`, a MoE layer, mean of the traced
steps)."""
import common
import flops_lm


def read(facts: dict, args: dict):
    reduced = facts.get("scopes")
    cell = (reduced or {}).get("scopes", {}).get(args["scope"])
    if not cell or not facts.get("trace_steps"):
        return None
    seconds = sum(cell.values()) / facts["trace_steps"]
    if seconds <= 0.0:
        return None
    work, extra = args["work"], {}
    if work == "experts":
        extra["assignments"] = facts["assignments_held"]
    shape = (facts["model_cfg"], *facts["lm_shape"])
    flops = getattr(flops_lm, work + "_train_flops")(*shape, **extra)
    moved = getattr(flops_lm, work + "_train_bytes")(*shape, **extra)
    peaks = common.peaks_for(facts["device_kind"])
    least = max(flops / peaks["bf16_flops"], moved / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
