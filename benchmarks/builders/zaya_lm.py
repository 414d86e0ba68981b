"""A `zaya` decoder (ZAYA1) cut to one chip's share of an expert-parallel
deployment: every width from the configuration file's published keys, the
router at its published width, `experts_held` of its experts computed
here, the vocabulary slice as the whole vocabulary. Besides the program's
configuration it hands `kinds/lm_train_steps_by_builder.py` everything
that is this family's: its plain reference, that reference's `hp`, the
forward that returns the picks, the rule by which the harness draws each
leaf of the weights, and the scopes its dry rehearsal makes up."""

# the program's `zaya` family first: on a tree without it this import
# fails before anything touches the device
from alphafold2_tpu.models.decoder import ZayaConfig

_PUBLISHED = ("hidden_size", "num_attention_heads", "num_key_value_heads",
              "head_dim", "moe_intermediate_size", "num_experts_per_tok",
              "router_hidden_size", "cca_time0", "cca_time1",
              "partial_rotary_factor", "rms_norm_eps", "tie_word_embeddings",
              "vocab_size")
# what models/decoder.py computes, whatever the file says
_COMPUTED = {"hidden_act": "silu", "attention_bias": False, "lm_head_bias": False,
             "sliding_window": None}

#: the projections that end a residual branch: `scaled_init_layers`
#: narrows them (the configuration file's `assumed.initializer`)
_BRANCH_ENDS = ("o", "down")
_ROUTER_MLP = ("fc1", "fc2", "fc3")
#: leaves that start at a constant
_CONSTANTS = {"scale": 1.0, "a": 1.0, "tau": 1.0, "gamma": 1.0,
              "c": 0.0, "b": 0.0, "bias": 0.0}


def leaf_rule(path, assumed: dict):
    """("normal", std) or ("constant", value) for the parameter leaf at
    `path` (its keys as strings), by the file's `assumed_values`: `table`
    and `w` N(0, initializer_range), the `w` that ends a residual branch
    N(0, initializer_range / sqrt(2 * scaled_init_layers)), the router
    MLP's N(0, router_mlp_std), the program's fixed scale for it
    (models/decoder.py `ROUTER_MLP_STD`; tests/test_zaya_cell.py holds the
    two equal); norms, `a`, `tau`, `gamma` 1; `c`, every bias and the
    balancing bias 0."""
    role = path[-1]
    if role in _CONSTANTS:
        return "constant", _CONSTANTS[role]
    if role == "table":
        return "normal", assumed["initializer_range"]
    if role == "w":
        if path[-2] in _BRANCH_ENDS:
            return "normal", (assumed["initializer_range"]
                              / (2.0 * assumed["scaled_init_layers"]) ** 0.5)
        if path[-2] in _ROUTER_MLP:
            return "normal", assumed["router_mlp_std"]
        return "normal", assumed["initializer_range"]
    raise ValueError(f"no rule for parameter leaf {'/'.join(path)}")


def reference_hp(cfg, tcfg) -> dict:
    return {"heads": cfg.num_attention_heads, "kv_heads": cfg.num_key_value_heads,
            "dh": cfg.head_dim, "rot": cfg.rotary_dim, "eps": cfg.rms_norm_eps,
            "theta": float(cfg.rope_theta), "top_k": cfg.num_experts_per_tok,
            "held": tuple(cfg.held), "lr": tcfg.learning_rate,
            "bias_rate": cfg.bias_update_rate}


def picks(params, cfg, tokens):
    """(layers, tokens, top_k): the experts the program's router picks."""
    from alphafold2_tpu.models.decoder import decoder_apply

    return decoder_apply(params, cfg, tokens)[1]["picks"]


def build(config: dict, dry: bool):
    from alphafold2_tpu.training import TrainConfig

    sizes = {key: config[key] for key in _PUBLISHED}
    # the depth that is run is the file's `layers`; its `num_hidden_layers`
    # stays the source's
    sizes["num_hidden_layers"] = config["layers"]
    router_width = config["published"]["num_experts"]
    held = tuple(config["experts_held"])
    if not dry and held[1] - held[0] != config["num_experts"]:
        raise SystemExit(f"configuration file holds {config['num_experts']} "
                         f"experts but experts_held is {held}")
    for key, want in _COMPUTED.items():
        if config[key] != want:
            raise SystemExit(f"the decoder computes {key}={want!r} only; the "
                             f"configuration file says {config[key]!r}")
    if set(config["layer_types"]) != {"hybrid"}:
        raise SystemExit("the decoder computes `hybrid` layers only")
    if dry:
        sizes.update(config["dry_args"]["sizes"],
                     num_hidden_layers=config["dry_args"]["layers"])
        router_width = config["dry_args"]["router_width"]
        held = tuple(config["dry_args"]["experts_held"])
    assumed = config["assumed_values"]
    cfg = ZayaConfig(
        num_experts=router_width, experts_held=held,
        rope_theta=float(config["rope_parameters"]["hybrid"]["rope_theta"]),
        bias_update_rate=assumed["bias_update_rate"],
        initializer_range=assumed["initializer_range"],
        scaled_init_layers=assumed["scaled_init_layers"],
        dtype="float32" if dry else config["dtype"], **sizes)
    tcfg = TrainConfig(learning_rate=config["train"]["learning_rate"],
                       grad_accum=config["train"]["grad_accum"])
    return {"cfg": cfg, "tcfg": tcfg, "reference": "zaya_lm",
            "reference_hp": reference_hp, "picks": picks, "leaf_rule": leaf_rule,
            "dry_scopes": ("cca_attn/attn_core", "cca_attn/qkv_proj",
                           "cca_attn/conv_mix", "cca_attn/qk_norm_rope",
                           "cca_attn/value_shift", "moe/experts", "moe/router",
                           "residual_scale", "lm_head_loss", "decoder_layers")}
