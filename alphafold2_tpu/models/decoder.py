"""A causal decoder language model of the `deepseek_v3` family: multi-head
latent attention (MLA) and a mixture of experts with shared experts.

Pure init/apply functions over a parameter pytree, as the rest of
`models/`. Every size comes from `DecoderConfig`, whose keys are the
published `config.json`'s; the layers are scanned (the leading dense
layers as one stack, the MoE layers as another), each layer under a
`jax.checkpoint` that keeps the causal kernel's `out` and `lse` for the
backward pass and builds the rest of the layer again (`_checkpointed_layer`).

The equations are HF `transformers` `deepseek_v3`'s:

  block   h += MLA(RMSNorm(h)); h += MLP(RMSNorm(h)); the first
          `first_k_dense_replace` layers' MLP is a SwiGLU of
          `intermediate_size`, the others' is the MoE (ops/moe.py);
          final RMSNorm; untied head (training/lm.py applies it).
  MLA     q = x W_q -> heads of `qk_nope_head_dim` | `qk_rope_head_dim`;
          [c | k_r] = x W_dkv; c = RMSNorm(c); [k_nope | v] = c W_ukv;
          RoPE on q's rope part and on the ONE k_r all heads share;
          k = [k_nope | k_r]; softmax(q k^T / sqrt(qk_head_dim)) under the
          causal mask; heads of `v_head_dim` through W_o.

Departures, each without effect on the logits:
  * `q_lora_rank` must be None (the published model's is): q is one
    projection, with no latent of its own;
  * RoPE rotates the interleaved pairs (x_2i, x_2i+1) in place, where HF
    first moves them to (x_i, x_i+d/2): the same fixed permutation of q's
    and k's lanes, so q k^T is the same;
  * training computes attention in this expanded form; absorbing W_ukv
    into q and W_o is a matter of decoding, which this module does not do;
  * no auxiliary sequence-balance loss (the config has none) and no
    cross-document mask (DeepSeek-V3 packs without one);
  * one chip's share: `experts_held` of the router's `n_routed_experts`
    are computed here (ops/moe.py) and `vocab_size` is the slice of the
    vocabulary this chip holds.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from alphafold2_tpu.ops import moe
from alphafold2_tpu.ops.core import embedding, linear
from alphafold2_tpu.ops.flash import causal_checkpoint_policy, flash_attention
from alphafold2_tpu.telemetry.profiling import scope


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kv_lora_rank: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int  # the router's width
    num_experts_per_tok: int
    n_shared_experts: int
    routed_scaling_factor: float
    first_k_dense_replace: int = 1
    norm_topk_prob: bool = True
    q_lora_rank: Optional[int] = None
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    # > 0: the projection that ends each residual branch (`o`, every
    # `down`) starts at initializer_range / sqrt(2 * scaled_init_layers),
    # GPT-2's and Megatron's scaled init for a model of that many layers.
    # At one scale for all, each position's hidden state is mostly the
    # attention's mean over the sequence, which every position shares: the
    # same few experts win everywhere (PERF.md section 6, PR 27)
    scaled_init_layers: int = 0
    # the experts this chip holds, [lo, hi) of n_routed_experts; None = all
    experts_held: Optional[Tuple[int, int]] = None
    # b_e += rate * sign(mean load - load_e) after each step
    bias_update_rate: float = 0.001
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.q_lora_rank is not None:
            raise ValueError("DecoderConfig: q_lora_rank must be None (q is "
                             "one projection; models/decoder.py)")
        lo, hi = self.held
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the {self.n_routed_experts} experts")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace exceeds num_hidden_layers")

    @property
    def held(self) -> Tuple[int, int]:
        return tuple(self.experts_held or (0, self.n_routed_experts))

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)


# --- init ---------------------------------------------------------------------

def _w(key, shape, std):
    return {"w": std * jax.random.normal(key, shape, jnp.float32)}


def _scale(dim, lead=()):
    return {"scale": jnp.ones(lead + (dim,), jnp.float32)}


def _out_std(cfg):
    """The scale of a residual branch's last projection."""
    if not cfg.scaled_init_layers:
        return cfg.initializer_range
    return cfg.initializer_range / (2.0 * cfg.scaled_init_layers) ** 0.5


def _swiglu_init(key, lead, d, f, cfg):
    kg, ku, kd = jax.random.split(key, 3)
    std = cfg.initializer_range
    return {"gate": _w(kg, lead + (d, f), std), "up": _w(ku, lead + (d, f), std),
            "down": _w(kd, lead + (f, d), _out_std(cfg))}


def _attn_init(key, n, cfg):
    d, h, std = cfg.hidden_size, cfg.num_attention_heads, cfg.initializer_range
    kq, kd, ku, ko = jax.random.split(key, 4)
    return {
        "q": _w(kq, (n, d, h * cfg.qk_head_dim), std),
        "dkv": _w(kd, (n, d, cfg.kv_lora_rank + cfg.qk_rope_head_dim), std),
        "kv_norm": _scale(cfg.kv_lora_rank, (n,)),
        "ukv": _w(ku, (n, cfg.kv_lora_rank,
                       h * (cfg.qk_nope_head_dim + cfg.v_head_dim)), std),
        "o": _w(ko, (n, h * cfg.v_head_dim, d), _out_std(cfg)),
    }


def _layers_init(key, n, cfg, mlp):
    ka, km = jax.random.split(key)
    return {"attn_norm": _scale(cfg.hidden_size, (n,)),
            "attn": _attn_init(ka, n, cfg),
            "mlp_norm": _scale(cfg.hidden_size, (n,)),
            "mlp": mlp(km)}


def decoder_init(key, cfg: DecoderConfig):
    """N(0, initializer_range) weights (`scaled_init_layers` narrows the
    residual branches' last projections), unit norms, zero selection bias;
    layers stacked on a leading axis (`dense`: the leading dense layers,
    `moe`: the rest)."""
    d, std = cfg.hidden_size, cfg.initializer_range
    ke, kd, km, kh = jax.random.split(key, 4)
    n_dense, n_moe = cfg.first_k_dense_replace, cfg.n_moe_layers
    lo, hi = cfg.held

    def moe_mlp(k):
        kr, kx, ks = jax.random.split(k, 3)
        return {
            "router": _w(kr, (n_moe, d, cfg.n_routed_experts), std),
            "bias": jnp.zeros((n_moe, cfg.n_routed_experts), jnp.float32),
            "experts": _swiglu_init(kx, (n_moe, hi - lo), d,
                                    cfg.moe_intermediate_size, cfg),
            "shared": _swiglu_init(
                ks, (n_moe,), d,
                cfg.n_shared_experts * cfg.moe_intermediate_size, cfg),
        }

    params = {
        "embed": {"table": std * jax.random.normal(
            ke, (cfg.vocab_size, d), jnp.float32)},
        "final_norm": _scale(d),
        "head": _w(kh, (d, cfg.vocab_size), std),
    }
    if n_dense:
        params["dense"] = _layers_init(
            kd, n_dense, cfg,
            lambda k: _swiglu_init(k, (n_dense,), d, cfg.intermediate_size, cfg))
    if n_moe:
        params["moe"] = _layers_init(km, n_moe, cfg, moe_mlp)
    return params


# --- apply --------------------------------------------------------------------

def rms_norm(params, x, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * params["scale"]).astype(x.dtype)


def rope(x, theta: float):
    """Rotate the interleaved pairs (x_2i, x_2i+1) of the last axis by
    position * theta^(-2i/d). x: (B, L, ..., d), positions 0..L-1."""
    L, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((1, L) + (1,) * (x.ndim - 3) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def mla_apply(params, x, cfg: DecoderConfig):
    """Multi-head latent attention, expanded form. x: (B, L, d)."""
    B, L, _ = x.shape
    h, dtype = cfg.num_attention_heads, cfg.compute_dtype
    nope, rd, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    with scope("qkv_proj"):
        q = linear(params["q"], x, dtype).reshape(B, L, h, nope + rd)
    with scope("kv_down_up"):
        ckr = linear(params["dkv"], x, dtype)
        c, k_r = ckr[..., :cfg.kv_lora_rank], ckr[..., cfg.kv_lora_rank:]
        c = rms_norm(params["kv_norm"], c, cfg.rms_norm_eps)
        kv = linear(params["ukv"], c, dtype).reshape(B, L, h, nope + dv)
        k_nope, v = kv[..., :nope], kv[..., nope:]
    with scope("rope"):
        q = jnp.concatenate(
            [q[..., :nope], rope(q[..., nope:], cfg.rope_theta)], axis=-1)
        k_r = rope(k_r[:, :, None, :], cfg.rope_theta)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_r, (B, L, h, rd))], axis=-1)
    out = flash_attention(q, k, v, causal=True, scale=cfg.qk_head_dim ** -0.5)
    with scope("out_proj"):
        return linear(params["o"], out.reshape(B, L, h * dv), dtype)


def _layer(lp, h, cfg: DecoderConfig, is_moe: bool):
    with scope("mla_attn"):
        h = h + mla_apply(lp["attn"], rms_norm(lp["attn_norm"], h,
                                               cfg.rms_norm_eps), cfg)
    B, L, d = h.shape
    if not is_moe:
        with scope("dense_mlp"):
            x = rms_norm(lp["mlp_norm"], h, cfg.rms_norm_eps)
            return h + moe.swiglu(lp["mlp"], x, cfg.compute_dtype), None
    with scope("moe"):
        x = rms_norm(lp["mlp_norm"], h, cfg.rms_norm_eps).reshape(B * L, d)
        y, aux = moe.moe_apply(
            lp["mlp"], x, top_k=cfg.num_experts_per_tok,
            scaling=cfg.routed_scaling_factor, norm_topk=cfg.norm_topk_prob,
            held=cfg.held)
        return h + y.reshape(B, L, d), aux


def _checkpointed_layer(cfg, is_moe):
    """One layer as the scans run it, (h, layer params) -> (h, aux), under
    the layer's `jax.checkpoint`. It keeps the causal kernel's `out` and
    `lse` (a residual stream's width twice over a layer, and what the
    backward kernel reads besides q, k, v) and builds everything else
    again in the backward pass, so the core's forward runs once a step.
    Where the core takes the XLA arm the layer holds no such name and is
    recomputed whole."""
    return jax.checkpoint(lambda h, lp: _layer(lp, h, cfg, is_moe),
                          policy=causal_checkpoint_policy())


def _stack(layers, h, cfg, is_moe):
    return jax.lax.scan(_checkpointed_layer(cfg, is_moe), h, layers)


def decoder_apply(params, cfg: DecoderConfig, tokens):
    """tokens (B, L) int -> (hidden (B, L, d) after the final norm, in the
    compute dtype; aux {"load": (n_moe, E), "picks": (n_moe, B*L, top_k)},
    empty without MoE layers)."""
    with scope("lm_embed"):
        # rows from the float32 table, so that the table's gradient adds
        # up in float32 however often a token repeats
        h = embedding(params["embed"], tokens).astype(cfg.compute_dtype)
    aux = {}
    # what the layer scans do themselves (a layer's slice of the stacked
    # parameters, its gradient's write-back, the carried residual stream)
    with scope("decoder_layers"):
        if "dense" in params:
            h, _ = _stack(params["dense"], h, cfg, False)
        if "moe" in params:
            h, aux = _stack(params["moe"], h, cfg, True)
    with scope("lm_head_loss"):
        h = rms_norm(params["final_norm"], h, cfg.rms_norm_eps)
    return h, aux
