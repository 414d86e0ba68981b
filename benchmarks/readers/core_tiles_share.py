"""The tiles the causal kernel's plan walks in a sliding-window layer as a
share of the triangle's, a fraction of 1 (unit `x`: 15 of 36 reads 0.4167;
at a length of one block the band IS the triangle and it reads 1): what
the program's own plan says
(`alphafold2_tpu.ops.flash.causal_kernel_plan(..., window=)`: `tiles` of
the band beside `tiles_triangle`) for the cell's length and the
configuration's heads and window. None where the configuration has no
window, or the program no plan for one (a tree from before the window)."""


def read(facts: dict, args: dict):
    cfg, shape = facts.get("model_cfg"), facts.get("lm_shape")
    window = getattr(cfg, "sliding_window", None)
    if shape is None or window is None:
        return None
    try:
        from alphafold2_tpu.ops.flash import causal_kernel_plan

        plan = causal_kernel_plan(shape[1], cfg.num_attention_heads, cfg.qk_head_dim,
                                  cfg.v_head_dim, cfg.compute_dtype, window=window)
    except (ImportError, TypeError):
        return None
    if not plan or not plan.get("tiles_triangle"):
        return None
    return plan["tiles"] / plan["tiles_triangle"]
