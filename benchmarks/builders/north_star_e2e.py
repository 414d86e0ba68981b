"""BASELINE config 5 as `training/presets.py north_star_e2e_config` builds
it: the one place the program defines it."""


def build(config: dict, dry: bool):
    from alphafold2_tpu.training import TrainConfig, north_star_e2e_config

    args = dict(config["dry_args"] if dry else config["builder_args"])
    ecfg, crop, msa_rows = north_star_e2e_config(**args)
    if not dry:
        m = ecfg.model
        ran = {"dim": m.dim, "heads": m.heads, "dim_head": m.dim_head,
               "depth": m.depth, "crop": crop, "msa_rows": msa_rows,
               "cross_attn_compress_ratio": m.cross_attn_compress_ratio,
               "refiner_dim": ecfg.refiner.dim, "refiner_depth": ecfg.refiner.depth,
               "mds_iters": ecfg.mds_iters}
        for key, value in ran.items():
            if config[key] != value:
                raise SystemExit(f"configuration file says {key}={config[key]}, "
                                 f"the program built {value}")
    tcfg = TrainConfig(learning_rate=config["train"]["learning_rate"],
                       grad_accum=config["train"]["grad_accum"])
    return {"ecfg": ecfg, "tcfg": tcfg, "crop": crop, "msa_rows": msa_rows}
