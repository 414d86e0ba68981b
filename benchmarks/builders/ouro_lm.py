"""An `ouro` decoder (Ouro, a looped language model) cut to one pipeline
stage: every width and `total_ut_steps` from the configuration file's
published keys, the first `layers` of the published 48 built, the
vocabulary whole. Besides the program's configuration it hands
`kinds/dense_lm_train_steps.py` everything that is this family's: its
plain reference, that reference's `hp`, the rule by which the harness
draws each leaf of the weights, and the scopes its dry rehearsal makes
up."""

# the program's `ouro` family first: on a tree without it this import
# fails before anything touches the device
from alphafold2_tpu.models.decoder import OuroConfig

_PUBLISHED = ("vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "head_dim", "intermediate_size", "total_ut_steps",
              "rope_theta", "rms_norm_eps", "tie_word_embeddings")
# what models/decoder.py computes, whatever the file says
_COMPUTED = {"hidden_act": "silu", "use_sliding_window": False, "sliding_window": None,
             "rope_scaling": None, "early_exit_threshold": 1}

#: the projections that end a residual branch: `scaled_init_layers`
#: narrows them (the configuration file's `assumed.initializer`)
_BRANCH_ENDS = ("o", "down")


def leaf_rule(path, assumed: dict):
    """("normal", std) or ("constant", value) for the parameter leaf at
    `path` (its keys as strings), by the file's `assumed_values`: `table`
    and `w` N(0, initializer_range) (the exit gate's weight among them),
    the `w` that ends a residual branch N(0, initializer_range / sqrt(2 *
    scaled_init_layers)); every norm's scale 1 (the four of a layer and
    the final one); the exit gate's bias `b` 0."""
    role = path[-1]
    if role == "scale":
        return "constant", 1.0
    if role == "b":
        return "constant", 0.0
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
            "dh": cfg.head_dim, "eps": cfg.rms_norm_eps, "theta": float(cfg.rope_theta),
            "passes": cfg.total_ut_steps, "beta": cfg.beta, "lr": tcfg.learning_rate}


def build(config: dict, dry: bool):
    from alphafold2_tpu.training import TrainConfig

    sizes = {key: config[key] for key in _PUBLISHED}
    for key, want in _COMPUTED.items():
        if config[key] != want:
            raise SystemExit(f"the decoder computes {key}={want!r} only; the "
                             f"configuration file says {config[key]!r}")
    if set(config["layer_types"]) != {"full_attention"}:
        raise SystemExit("the decoder computes `full_attention` layers only")
    # the depth that is run is the file's `layers`; `num_hidden_layers` and
    # the length of `layer_types` stay the source's
    layers = config["layers"]
    if dry:
        sizes.update(config["dry_args"]["sizes"])
        layers = config["dry_args"]["layers"]
    assumed = config["assumed_values"]
    cfg = OuroConfig(
        layers=layers, initializer_range=assumed["initializer_range"],
        scaled_init_layers=assumed["scaled_init_layers"], beta=assumed["beta"],
        dtype="float32" if dry else config["dtype"], **sizes)
    tcfg = TrainConfig(learning_rate=config["train"]["learning_rate"],
                       grad_accum=config["train"]["grad_accum"])
    return {"cfg": cfg, "tcfg": tcfg, "reference": "ouro_lm",
            "reference_hp": reference_hp, "leaf_rule": leaf_rule,
            "dry_scopes": ("gqa_attn/attn_core", "gqa_attn/qkv_proj",
                           "gqa_attn/qk_norm_rope", "gqa_attn/out_proj", "dense_mlp",
                           "sandwich_norm", "exit_gate", "lm_head_loss",
                           "decoder_layers")}
