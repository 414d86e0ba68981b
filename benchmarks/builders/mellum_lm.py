"""A `mellum` decoder (Mellum 2) cut to one chip's share of an
expert-parallel deployment: every width from the configuration file's
published keys, the router at its published width, `experts_held` of its
experts computed here, the vocabulary slice as the whole vocabulary, the
first `layers` of `layer_types` (whole periods). Besides the program's
configuration it hands `kinds/lm_train_steps_by_builder.py` everything
that is this family's: its plain reference, that reference's `hp`, the
forward that returns the picks, the rule by which the harness draws each
leaf of the weights, and the scopes its dry rehearsal makes up."""

# the program's `mellum` family first: on a tree without it this import
# fails before anything touches the device
from alphafold2_tpu.models.decoder import MellumConfig

_PUBLISHED = ("hidden_size", "num_attention_heads", "num_key_value_heads",
              "head_dim", "moe_intermediate_size", "num_experts_per_tok",
              "norm_topk_prob", "rms_norm_eps", "tie_word_embeddings",
              "sliding_window", "rope_parameters", "vocab_size")
# what models/decoder.py computes, whatever the file says
_COMPUTED = {"hidden_act": "silu", "attention_bias": False,
             "use_sliding_window": True}

#: the projections that end a residual branch: `scaled_init_layers`
#: narrows them (the configuration file's `assumed.initializer`)
_BRANCH_ENDS = ("o", "down")


def leaf_rule(path, assumed: dict):
    """("normal", std) or ("constant", value) for the parameter leaf at
    `path` (its keys as strings), by the file's `assumed_values`: `table`
    and `w` N(0, initializer_range), the `w` that ends a residual branch
    N(0, initializer_range / sqrt(2 * scaled_init_layers)); every norm's
    scale 1 (the layers', the final one, q's and k's by head). The family
    has no bias of any kind."""
    role = path[-1]
    if role == "scale":
        return "constant", 1.0
    if role == "table":
        return "normal", assumed["initializer_range"]
    if role == "w":
        if path[-2] in _BRANCH_ENDS:
            return "normal", (assumed["initializer_range"]
                              / (2.0 * assumed["scaled_init_layers"]) ** 0.5)
        return "normal", assumed["initializer_range"]
    raise ValueError(f"no rule for parameter leaf {'/'.join(path)}")


def reference_hp(cfg, tcfg) -> dict:
    return {"heads": cfg.num_attention_heads, "kv_heads": cfg.num_key_value_heads,
            "dh": cfg.head_dim, "eps": cfg.rms_norm_eps,
            "top_k": cfg.num_experts_per_tok, "norm_topk": cfg.norm_topk_prob,
            "held": tuple(cfg.held), "lr": tcfg.learning_rate,
            "layer_types": tuple(cfg.layer_types), "window": cfg.sliding_window,
            "rope": cfg.rope_parameters}


def picks(params, cfg, tokens):
    """(layers, tokens, top_k): the experts the program's router picks."""
    from alphafold2_tpu.models.decoder import decoder_apply

    return decoder_apply(params, cfg, tokens)[1]["picks"]


def build(config: dict, dry: bool):
    from alphafold2_tpu.training import TrainConfig

    sizes = {key: config[key] for key in _PUBLISHED}
    # the depth that is run is the file's `layers`; its `num_hidden_layers`
    # and the length of its `layer_types` stay the source's
    layers = config["layers"]
    router_width = config["published"]["num_experts"]
    held = tuple(config["experts_held"])
    if not dry and held[1] - held[0] != config["num_experts"]:
        raise SystemExit(f"configuration file holds {config['num_experts']} "
                         f"experts but experts_held is {held}")
    for key, want in _COMPUTED.items():
        if config[key] != want:
            raise SystemExit(f"the decoder computes {key}={want!r} only; the "
                             f"configuration file says {config[key]!r}")
    if set(config["mlp_layer_types"]) != {"sparse"}:
        raise SystemExit("the decoder computes `sparse` feed-forward layers only")
    if dry:
        sizes.update(config["dry_args"]["sizes"])
        layers = config["dry_args"]["layers"]
        router_width = config["dry_args"]["router_width"]
        held = tuple(config["dry_args"]["experts_held"])
    assumed = config["assumed_values"]
    cfg = MellumConfig(
        num_hidden_layers=layers, layer_types=config["layer_types"][:layers],
        num_experts=router_width, experts_held=held,
        initializer_range=assumed["initializer_range"],
        scaled_init_layers=assumed["scaled_init_layers"],
        dtype="float32" if dry else config["dtype"], **sizes)
    tcfg = TrainConfig(learning_rate=config["train"]["learning_rate"],
                       grad_accum=config["train"]["grad_accum"])
    return {"cfg": cfg, "tcfg": tcfg, "reference": "mellum_lm",
            "reference_hp": reference_hp, "picks": picks, "leaf_rule": leaf_rule,
            "dry_scopes": ("gqa_attn/attn_core_window", "gqa_attn/attn_core",
                           "gqa_attn/qkv_proj", "gqa_attn/qk_norm_rope",
                           "gqa_attn/out_proj", "moe/experts", "moe/router",
                           "lm_head_loss", "decoder_layers")}
