"""A kernel's share of its roofline over the traced steps, in percent:
the least time the chip could take for the work ASKED of the scope in one
step (the larger of required FLOPs / bf16 peak and required bytes / HBM
peak, whatever arm or kernel does the work) over the scope's device self
seconds a step, all phases (`scope_reduce.reduce_scopes`, kept as
`facts["scopes"]`). `args`: `scope` (a key of the table); `work`
(`attn_core` or `experts`, naming `<module>.<work>_train_flops` /
`_bytes`); `module`, the benchmark's file that counts it (`flops_lm`
where left out; `flops` for the trunk) and `shape`, the fact that holds
the shape those functions take after the model's configuration
(`lm_shape` where left out; `grid`). The experts' count takes the
assignments the router really gave (`facts["assignments_held"]`, a MoE
layer, mean of the traced steps)."""
import importlib

import common


def read(facts: dict, args: dict):
    reduced = facts.get("scopes")
    cell = (reduced or {}).get("scopes", {}).get(args["scope"])
    shape = facts.get(args.get("shape", "lm_shape"))
    if not cell or not facts.get("trace_steps") or shape is None:
        return None
    seconds = sum(cell.values()) / facts["trace_steps"]
    if seconds <= 0.0:
        return None
    work, extra = args["work"], {}
    if work == "experts":
        extra["assignments"] = facts["assignments_held"]
    counts = importlib.import_module(args.get("module", "flops_lm"))
    flops = getattr(counts, work + "_train_flops")(facts["model_cfg"], *shape, **extra)
    moved = getattr(counts, work + "_train_bytes")(facts["model_cfg"], *shape, **extra)
    peaks = common.peaks_for(facts["device_kind"])
    least = max(flops / peaks["bf16_flops"], moved / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
