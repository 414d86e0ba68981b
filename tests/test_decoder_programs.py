"""The decoder families that were there before `ouro` lower to the programs
they lowered to before it: `mellum`'s whole train step (the family whose
`gqa_apply` now skips a q / k norm that a layer does not have) and every
family's loss with its gradient, at toy widths on the CPU, by the sha256
of the lowered StableHLO text (locations are not in the text). The
hashes were read on the tree before `ouro` was added (commit b77b2ee);
a change that moves one of them changes that family's program."""
import hashlib

import jax
import jax.numpy as jnp
import pytest

from alphafold2_tpu.models.decoder import (FULL, SLIDING, DecoderConfig,
                                           MellumConfig, ZayaConfig)
from alphafold2_tpu.training.harness import TrainConfig, make_optimizer, make_train_step
from alphafold2_tpu.training.lm import lm_aux_update, lm_loss_fn, lm_params_init

CFGS = {
    "deepseek_v3": DecoderConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2, num_attention_heads=2,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, kv_lora_rank=16,
        intermediate_size=64, moe_intermediate_size=16, n_routed_experts=4,
        num_experts_per_tok=2, n_shared_experts=1, routed_scaling_factor=2.0,
        experts_held=(0, 2), dtype="float32"),
    "zaya": ZayaConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=2, head_dim=8, moe_intermediate_size=16, num_experts=4,
        router_hidden_size=8, experts_held=(0, 2), dtype="float32"),
    "mellum": MellumConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=3, num_attention_heads=4,
        num_key_value_heads=2, head_dim=8, moe_intermediate_size=16, num_experts=4,
        num_experts_per_tok=2, layer_types=(SLIDING, SLIDING, FULL), sliding_window=6,
        rope_parameters={SLIDING: {"rope_theta": 1e4}, FULL: {"rope_theta": 1e4}},
        experts_held=(0, 2), dtype="bfloat16"),
}

PARENT = {
    ("deepseek_v3", "loss"): "3c983d48747ca2f64a0bb09c69a52560424b5939abcf99344621bc15072cddb5",
    ("zaya", "loss"): "5facc7a57e15c294339e1a46c05783fdf5c265dc3945157b0d8d840f8a2e9e0d",
    ("mellum", "loss"): "707e9c1e5232f4959ba45f24d28d7067124896e48f710b23098b6e251111edca",
    ("mellum", "step"): "a72c4ffb98373ced1e4e34761635f27890ec48a35c8c998ea373bc1b00521068",
}


def _lowered(family, what):
    cfg = CFGS[family]
    params = jax.eval_shape(lambda k: lm_params_init(k, cfg), jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    if what == "loss":
        fn = jax.value_and_grad(lambda p, t: lm_loss_fn(p, cfg, {"tokens": t}),
                                has_aux=True)
        return jax.jit(fn).lower(params, tokens)
    tcfg = TrainConfig(grad_accum=1)
    state = {"params": params, "opt_state": jax.eval_shape(make_optimizer(tcfg).init, params),
             "step": jax.ShapeDtypeStruct((), jnp.int32)}
    step = make_train_step(cfg, tcfg, loss_fn=lm_loss_fn, aux_update=lm_aux_update(cfg))
    return jax.jit(step, donate_argnums=(0,)).lower(
        state, {"tokens": jax.ShapeDtypeStruct((1, 2, 16), jnp.int32)}, None)


@pytest.mark.parametrize("family,what", sorted(PARENT), ids=lambda v: str(v))
def test_the_program_is_the_parents(family, what):
    text = _lowered(family, what).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT[family, what]
