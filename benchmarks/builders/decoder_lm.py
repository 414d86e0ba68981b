"""A `deepseek_v3` decoder cut to one chip's share of an expert-parallel
deployment: every width from the configuration file's published keys, the
router at its published width, `experts_held` of its experts computed
here, the vocabulary slice as the whole vocabulary."""

# the program's decoder first: on a tree without it this import fails
# before anything touches the device
from alphafold2_tpu.models.decoder import DecoderConfig

_PUBLISHED = ("hidden_size", "num_attention_heads", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "q_lora_rank",
              "intermediate_size", "moe_intermediate_size", "num_experts_per_tok",
              "n_shared_experts", "routed_scaling_factor", "first_k_dense_replace",
              "norm_topk_prob", "rope_theta", "rms_norm_eps", "vocab_size")
# what models/decoder.py and ops/moe.py compute, whatever the file says
_COMPUTED = {"scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
             "topk_group": 1, "rope_interleave": True, "hidden_act": "silu"}


def build(config: dict, dry: bool):
    from alphafold2_tpu.training import TrainConfig

    sizes = {key: config[key] for key in _PUBLISHED}
    # the depth that is run is the file's `layers`; its `num_hidden_layers`
    # stays the source's
    sizes["num_hidden_layers"] = config["layers"]
    router_width = config["published"]["n_routed_experts"]
    held = tuple(config["experts_held"])
    if not dry and held[1] - held[0] != config["n_routed_experts"]:
        raise SystemExit(f"configuration file holds {config['n_routed_experts']} "
                         f"experts but experts_held is {held}")
    for key, want in _COMPUTED.items():
        if config[key] != want:
            raise SystemExit(f"the decoder computes {key}={want!r} only; the "
                             f"configuration file says {config[key]!r}")
    if dry:
        sizes.update(config["dry_args"]["sizes"],
                     num_hidden_layers=config["dry_args"]["layers"])
        router_width = config["dry_args"]["router_width"]
        held = tuple(config["dry_args"]["experts_held"])
    cfg = DecoderConfig(
        n_routed_experts=router_width, experts_held=held,
        bias_update_rate=config["assumed_values"]["bias_update_rate"],
        initializer_range=config["assumed_values"]["initializer_range"],
        scaled_init_layers=config["assumed_values"]["scaled_init_layers"],
        dtype="float32" if dry else config["dtype"], **sizes)
    tcfg = TrainConfig(learning_rate=config["train"]["learning_rate"],
                       grad_accum=config["train"]["grad_accum"])
    return {"cfg": cfg, "tcfg": tcfg}
