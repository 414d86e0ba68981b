"""Causal decoder language models of FOUR families, chosen by the class
of the configuration that `decoder_init` / `decoder_apply` are handed (a
file's `model_type` picks the class: train_lm.py `config_from_file`):

  * `DecoderConfig`, `deepseek_v3`: multi-head latent attention (MLA) and
    a mixture of experts with shared experts;
  * `ZayaConfig`, `zaya` (ZAYA1): compressed convolutional attention (CCA)
    in a latent narrower than the residual stream with grouped keys, a
    top-1 mixture picked by an MLP router that carries a state from layer
    to layer, a scaled residual stream, a tied head;
  * `MellumConfig`, `mellum` (Mellum 2): grouped-query attention with a
    per-head RMSNorm on q and k, sliding-window and full causal layers
    mixed by `layer_types`, each kind with its own position table (YaRN on
    the full layers), a softmax top-k mixture of narrow experts with no
    shared expert and no balancing bias, an untied head;
  * `OuroConfig`, `ouro` (Ouro, a looped language model): a dense stack of
    multi-head attention and SwiGLU layers with an RMSNorm before AND after
    each sublayer, the whole stack run `total_ut_steps` times over the same
    weights, each pass ending in the final norm, an untied head and an exit
    gate; the only family with no expert layer.

Pure init/apply functions over a parameter pytree, as the rest of
`models/`. Every size comes from the configuration, whose keys are the
published `config.json`'s; the layers are scanned (`deepseek_v3`: the
leading dense layers as one stack, the MoE layers as another; `zaya`: one
stack whose carry is the residual stream AND the router's state; `mellum`:
one stack in the published order, scanned a whole period of `layer_types`
at a time with each run of one kind scanned inside it, so that the window
is static where the core is called; `ouro`: one stack, scanned once a
pass), each
layer under a `jax.checkpoint` that keeps the causal kernel's `out` and
`lse` for the backward pass and builds the rest of the layer again
(`_checkpointed_layer`). All share the skeleton, the causal core (ops/flash.py), the
expert layer after its router (ops/moe.py) and the loss (training/lm.py).

`deepseek_v3`'s equations are HF `transformers`':

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

`zaya`'s equations are the CCA paper's (Figliolia et al., "Compressed
Convolutional Attention", arXiv:2510.04476, the CCGQA form) and the ZAYA1
report's (Anthony et al., arXiv:2511.17127: CCA, the ZAYA1 router,
residual scaling). d the hidden size, h query heads and hk key heads of
dh lanes, g = h / hk, x_t a sublayer's RMSNorm'd input; every convolution
pads on the left only, so nothing sees a later token:

  block   for each sublayer f (CCA, then experts) with its own RMSNorm:
          h = (a * h + c) + f(RMSNorm(h)), a and c learned vectors of d;
          final RMSNorm; logits = h E^T, E the embedding table.
  CCA     q0 = x W_q (d -> h dh), k0 = x W_k (d -> hk dh), no bias;
          [qc | kc] = Conv_b(Conv_a([q0 | k0])) over the sequence: Conv_a
          depthwise (one filter of `cca_time0` taps a channel), Conv_b
          grouped by head (dh -> dh channels a head, `cca_time1` taps),
          both with bias; the mean of q and k goes around them:
          q = qc + (q0 + repeat_g(k0)) / 2, k = kc + (mean_g(q0) + k0) / 2
          by head; q = sqrt(dh) q / |q|, k = sqrt(dh) tau k / |k| over a
          head's lanes, tau one learned scalar a key head;
          v = [x_t W_v1 | x_(t-1) W_v2] (d -> hk dh / 2 each, x_(-1) = 0):
          the first half of the key heads' values are of this token, the
          second half's of the one before; RoPE on the first
          `partial_rotary_factor` of each head's lanes of q and k; causal
          softmax(q k^T / sqrt(dh)), each key head serving its g query
          heads; the h dh outputs through W_o.
  router  r_l = RMSNorm(x) W_down (d -> `router_hidden_size`);
          r_l += gamma_l * r_(l-1) (gamma_l a learned vector, r_(-1) = 0:
          the state the layer scan carries); s = W_3 gelu(W_2 gelu(W_1
          r_l)) with biases; p = softmax(s) in float32; the pick is
          argmax(p + b), b the balancing bias (ops/moe.py `route_softmax`,
          `bias_update`); the sublayer gives p[pick] SwiGLU_pick(x).

Departures and what the published config does not fix (the configuration
file's `assumed` argues each): RoPE rotates interleaved pairs, as above (a
fixed permutation of lanes that the convolutions' and projections' weights
absorb); gelu is the tanh form; no mixture-of-depths arm (the config has
no key for one); the balancing bias moves by `bias_update`, not by the
report's own controller; no cross-document mask; `tie_word_embeddings`
must be true; one chip's share as above (`experts_held`, with no shared
expert a token whose expert is absent gets nothing from the sublayer).

`mellum`'s equations follow from the published keys (`model_type: mellum`;
the key set is the Qwen3-MoE family's plus `layer_types` and a
`rope_parameters` section a layer kind). d the hidden size, h query heads
and hk key heads of dh lanes (dh is its own key, not d / h), g = h / hk:

  block   h += GQA_l(RMSNorm(h)); h += MoE(RMSNorm(h)) for every layer
          (`mlp_layer_types` all `sparse`: no leading dense layer); final
          RMSNorm; logits = h W_head (untied).
  GQA     q = x W_q (d -> h dh), k = x W_k, v = x W_v (d -> hk dh each), no
          bias; RMSNorm over the dh lanes of each head of q and of k, each
          with its own learned scale that the heads share; RoPE over all dh
          lanes; softmax(q k^T / sqrt(dh) + mask_l), key head j serving
          query heads [j g, (j + 1) g); the h dh outputs through W_o.
  kinds   by `layer_types`. `sliding_attention`: query i sees keys j with
          i - `sliding_window` < j <= i (that many keys, its own among
          them: `transformers`' convention) and turns by
          `rope_parameters.sliding_attention` (plain RoPE).
          `full_attention`: j <= i, and `rope_parameters.full_attention`:
          YaRN as `transformers` computes it, statically, whatever the
          length (`yarn_inv_freq`): with f_i = theta^(-2i/dh), inv_freq_i =
          f_i / factor * (1 - m_i) + f_i * m_i, m_i = 1 - clip((i - low) /
          (high - low), 0, 1), [low, high] the correction range of
          (`beta_fast`, `beta_slow`, dh, theta,
          `original_max_position_embeddings`); cos and sin are multiplied
          by `attention_factor`, so a full layer's logits carry its square.
  MoE     p = softmax(x W_r) over ALL `num_experts` in float32; the
          `num_experts_per_tok` largest; with `norm_topk_prob` their weights
          divided by their sum; y = sum_e w_e SwiGLU_e(x) at
          `moe_intermediate_size`. No shared expert, no selection bias, no
          auxiliary loss (the config has no key for any).

Departures and what the published config does not fix (the configuration
file's `assumed` argues each): the per-head q / k RMSNorm and softmax
before top-k are the Qwen3-MoE family's, whose key set this is; the
multi-token-prediction head the model card mentions has no key and is left
out; RoPE rotates interleaved pairs, as above; no cross-document mask;
`tie_word_embeddings` must be false; one chip's share as above.

`ouro`'s equations follow from the published keys (`model_type: ouro`; the
Ouro paper, Zhu et al., "Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741). d the hidden size, h query and h key heads of
dh lanes (no grouping), T = `total_ut_steps`:

  layer   a = Attn(RMSNorm_in(h)); h = h + RMSNorm_in2(a);
          m = SwiGLU(RMSNorm_post(h)); h = h + RMSNorm_post2(m): four
          norms a layer, each with its own learned scale (the sandwich).
  Attn    q, k, v = x W_q, x W_k, x W_v (d -> h dh each), no bias and no
          q / k norm (`gqa_apply` at g = 1 on a layer without the norms);
          RoPE over all dh lanes at `rope_theta`; softmax(q k^T / sqrt(dh))
          under the causal mask; W_o. SwiGLU(x) = (silu(x W_gate) *
          x W_up) W_down at `intermediate_size`.
  passes  h(0) = Embed(x); h(t) = RMSNorm_f(Layers(h(t-1))) for t = 1..T,
          the SAME `layers` layers with the same weights in every pass; the
          normed state is what the next pass reads and what the pass's head
          (logits(t) = h(t) W_head, untied) and exit gate (lambda_t =
          sigmoid(h(t) w_g + b_g), a Linear(d, 1) with a bias, in float32)
          read.
  exit    p(t) = lambda_t prod_{j<t} (1 - lambda_j) for t < T, p(T) =
          prod_{j<T} (1 - lambda_j); the loss (training/lm.py) is the mean
          over targets of sum_t p(t) CE_t - beta H(p), the paper's stage I.

Departures and what the published config does not fix (the configuration
file's `assumed` argues each): the sandwich norms and the gate's input
and bias come from the paper and the family's modelling code, not from a
key; `beta` has no key; the stage-II gate training and early exit are left
out (`early_exit_threshold` 1 runs every pass); RoPE rotates interleaved
pairs, as above; no cross-document mask; `layers` of the published
`num_hidden_layers` are built (a pipeline stage's share).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import ClassVar, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from alphafold2_tpu.ops import moe
from alphafold2_tpu.ops.core import embedding, linear
from alphafold2_tpu.ops.flash import core_checkpoint_policy, flash_attention
from alphafold2_tpu.telemetry.profiling import scope


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    model_type: ClassVar[str] = "deepseek_v3"
    router_width_key: ClassVar[str] = "n_routed_experts"

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


@dataclasses.dataclass(frozen=True)
class ZayaConfig:
    model_type: ClassVar[str] = "zaya"
    router_width_key: ClassVar[str] = "num_experts"

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    moe_intermediate_size: int
    num_experts: int  # the router's width
    num_experts_per_tok: int = 1
    router_hidden_size: int = 256
    cca_time0: int = 2
    cca_time1: int = 2
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5000000.0
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    initializer_range: float = 0.02
    scaled_init_layers: int = 0  # as DecoderConfig's
    experts_held: Optional[Tuple[int, int]] = None
    bias_update_rate: float = 0.001
    dtype: str = "bfloat16"

    def __post_init__(self):
        if not self.tie_word_embeddings:
            raise ValueError("ZayaConfig: tie_word_embeddings must be true "
                             "(the head is the embedding table)")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("ZayaConfig: the key heads must divide the query heads")
        if self.num_key_value_heads % 2:
            raise ValueError("ZayaConfig: half of the key heads take the "
                             "previous token's values, so they must be even")
        lo, hi = self.held
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the {self.num_experts} experts")

    @property
    def held(self) -> Tuple[int, int]:
        return tuple(self.experts_held or (0, self.num_experts))

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor) // 2 * 2

    @property
    def qk_head_dim(self) -> int:
        return self.head_dim

    @property
    def v_head_dim(self) -> int:
        return self.head_dim

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)


#: `mellum`'s two kinds of layer
SLIDING, FULL = "sliding_attention", "full_attention"


def _frozen(tree):
    """A nested dict as nested tuples of sorted (key, value) pairs, so that
    a frozen configuration stays hashable; `dict(...)` of a level undoes it."""
    if isinstance(tree, dict):
        return tuple(sorted((k, _frozen(v)) for k, v in tree.items()))
    return tree


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    model_type: ClassVar[str] = "mellum"
    router_width_key: ClassVar[str] = "num_experts"

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    moe_intermediate_size: int
    num_experts: int  # the router's width
    num_experts_per_tok: int
    # one kind a layer, in order: whole periods, a period a run of
    # `sliding_attention` layers that one `full_attention` layer ends
    layer_types: Tuple[str, ...]
    # keys a query of a `sliding_attention` layer sees, its own among them;
    # None: such a layer sees every key before it, as a full layer does
    sliding_window: Optional[int]
    # the published dict, a section a layer kind (a dict is taken and kept
    # as nested tuples; `rope_of` gives a section back as a dict)
    rope_parameters: tuple
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    scaled_init_layers: int = 0  # as DecoderConfig's
    experts_held: Optional[Tuple[int, int]] = None
    dtype: str = "bfloat16"

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        object.__setattr__(self, "rope_parameters", _frozen(self.rope_parameters))
        if self.tie_word_embeddings:
            raise ValueError("MellumConfig: tie_word_embeddings must be false "
                             "(the head is a projection of its own)")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("MellumConfig: the key heads must divide the query heads")
        unknown = set(self.layer_types) - {SLIDING, FULL}
        if unknown:
            raise ValueError(f"MellumConfig: layer_types has {sorted(unknown)}; "
                             f"a layer is {SLIDING!r} or {FULL!r}")
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(f"MellumConfig: {len(self.layer_types)} layer_types "
                             f"for {self.num_hidden_layers} layers")
        period = self.period
        if period * (self.num_hidden_layers // len(period)) != self.layer_types:
            raise ValueError(
                f"MellumConfig: layer_types {self.layer_types} is no whole "
                f"number of its period {period}")
        for kind in period:
            if kind not in dict(self.rope_parameters):
                raise ValueError(f"MellumConfig: rope_parameters has no {kind!r}")
        lo, hi = self.held
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the {self.num_experts} experts")

    @property
    def held(self) -> Tuple[int, int]:
        return tuple(self.experts_held or (0, self.num_experts))

    @property
    def period(self) -> Tuple[str, ...]:
        """The layers up to the first full one: what the stack repeats."""
        if FULL not in self.layer_types:
            return self.layer_types[:1]
        return self.layer_types[:self.layer_types.index(FULL) + 1]

    def window_of(self, kind: str) -> Optional[int]:
        return self.sliding_window if kind == SLIDING else None

    def rope_of(self, kind: str) -> dict:
        return dict(dict(self.rope_parameters)[kind])

    @property
    def qk_head_dim(self) -> int:
        return self.head_dim

    @property
    def v_head_dim(self) -> int:
        return self.head_dim

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)


@dataclasses.dataclass(frozen=True)
class OuroConfig:
    model_type: ClassVar[str] = "ouro"

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int  # the published depth
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int
    total_ut_steps: int  # T: the passes of the stack
    # the depth that is built and run, of num_hidden_layers; None: all
    layers: Optional[int] = None
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    scaled_init_layers: int = 0  # as DecoderConfig's
    # the weight of the exit distribution's entropy in the loss
    beta: float = 0.1
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.layers is None:
            object.__setattr__(self, "layers", self.num_hidden_layers)
        if self.total_ut_steps < 1:
            raise ValueError(f"OuroConfig: total_ut_steps {self.total_ut_steps}; "
                             "the stack runs at least once")
        if not 1 <= self.layers <= self.num_hidden_layers:
            raise ValueError(f"OuroConfig: layers {self.layers} is not a depth of "
                             f"the {self.num_hidden_layers} published layers")
        if self.tie_word_embeddings:
            raise ValueError("OuroConfig: tie_word_embeddings must be false "
                             "(the head is a projection of its own)")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("OuroConfig: the key heads must divide the query heads")

    def window_of(self, kind: str) -> Optional[int]:
        return None

    def rope_of(self, kind: str) -> dict:
        return {"rope_type": "default", "rope_theta": self.rope_theta}

    @property
    def qk_head_dim(self) -> int:
        return self.head_dim

    @property
    def v_head_dim(self) -> int:
        return self.head_dim

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


def _cca_init(key, n, cfg: ZayaConfig):
    d, std = cfg.hidden_size, cfg.initializer_range
    h, hk, dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    kq, kk, ka, kb, k1, k2, ko = jax.random.split(key, 7)
    zeros = jnp.zeros((n, (h + hk) * dh), jnp.float32)
    return {
        "q": _w(kq, (n, d, h * dh), std),
        "k": _w(kk, (n, d, hk * dh), std),
        # taps oldest first: tap j reads position t - (taps - 1) + j
        "conv_a": {**_w(ka, (n, cfg.cca_time0, (h + hk) * dh), std), "b": zeros},
        "conv_b": {**_w(kb, (n, cfg.cca_time1, h + hk, dh, dh), std), "b": zeros},
        "tau": jnp.ones((n, hk), jnp.float32),
        "v1": _w(k1, (n, d, hk * dh // 2), std),
        "v2": _w(k2, (n, d, hk * dh // 2), std),
        "o": _w(ko, (n, h * dh, d), _out_std(cfg)),
    }


#: the scale the router MLP's three layers start at, whatever
#: `initializer_range` is. At N(0, 0.02) the scores out of the three layers
#: spread 0.009 and softmax is flat to 5e-4 across the experts: less than
#: one step (0.001) of `ops/moe.py bias_update`, so after the first step
#: every token follows the balancing bias and the load swings between the
#: experts from step to step (PERF.md section 6, PR 32: both scales on the
#: chip, the same seeds)
ROUTER_MLP_STD = 0.04


def _router_init(key, n, cfg: ZayaConfig):
    d, r, std = cfg.hidden_size, cfg.router_hidden_size, cfg.initializer_range
    kd, k1, k2, k3 = jax.random.split(key, 4)

    def fc(k, fan_in, fan_out):
        return {**_w(k, (n, fan_in, fan_out), ROUTER_MLP_STD),
                "b": jnp.zeros((n, fan_out), jnp.float32)}

    return {"norm": _scale(d, (n,)), "reduce": _w(kd, (n, d, r), std),
            "gamma": jnp.ones((n, r), jnp.float32),
            "fc1": fc(k1, r, r), "fc2": fc(k2, r, r),
            "fc3": fc(k3, r, cfg.num_experts)}


def _residual_init(d, n):
    return {"a": jnp.ones((n, d), jnp.float32), "c": jnp.zeros((n, d), jnp.float32)}


def zaya_init(key, cfg: ZayaConfig):
    """As `deepseek_init`; besides: convolution and router-MLP biases 0,
    `tau`, `gamma` and the residual scale `a` 1, its shift `c` 0. ONE
    stack, `moe` (every layer has the expert sublayer); no `head`."""
    d, n = cfg.hidden_size, cfg.num_hidden_layers
    lo, hi = cfg.held
    ke, ka, kr, kx = jax.random.split(key, 4)
    layers = {
        "attn_res": _residual_init(d, n), "attn_norm": _scale(d, (n,)),
        "attn": _cca_init(ka, n, cfg),
        "mlp_res": _residual_init(d, n), "mlp_norm": _scale(d, (n,)),
        "mlp": {"router": _router_init(kr, n, cfg),
                "bias": jnp.zeros((n, cfg.num_experts), jnp.float32),
                "experts": _swiglu_init(kx, (n, hi - lo), d,
                                        cfg.moe_intermediate_size, cfg)},
    }
    return {"embed": {"table": cfg.initializer_range * jax.random.normal(
                ke, (cfg.vocab_size, d), jnp.float32)},
            "final_norm": _scale(d), "moe": layers}


def mellum_init(key, cfg: MellumConfig):
    """As `deepseek_init`, without a selection bias: ONE stack, `moe`, the
    layers of both kinds in the published order; the q / k norms' scales 1."""
    d, n, std = cfg.hidden_size, cfg.num_hidden_layers, cfg.initializer_range
    h, hk, dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    lo, hi = cfg.held
    ke, kq, kk, kv, ko, kr, kx, kh = jax.random.split(key, 8)
    layers = {
        "attn_norm": _scale(d, (n,)),
        "attn": {"q": _w(kq, (n, d, h * dh), std), "k": _w(kk, (n, d, hk * dh), std),
                 "v": _w(kv, (n, d, hk * dh), std),
                 "q_norm": _scale(dh, (n,)), "k_norm": _scale(dh, (n,)),
                 "o": _w(ko, (n, h * dh, d), _out_std(cfg))},
        "mlp_norm": _scale(d, (n,)),
        "mlp": {"router": _w(kr, (n, d, cfg.num_experts), std),
                "experts": _swiglu_init(kx, (n, hi - lo), d,
                                        cfg.moe_intermediate_size, cfg)},
    }
    return {"embed": {"table": std * jax.random.normal(
                ke, (cfg.vocab_size, d), jnp.float32)},
            "final_norm": _scale(d), "head": _w(kh, (d, cfg.vocab_size), std),
            "moe": layers}


def ouro_init(key, cfg: OuroConfig):
    """As `mellum_init`: ONE stack, `dense` (every layer is of one kind),
    with four norms a layer, each scale 1, and no q / k norm; beside it the
    exit gate, a Linear(d, 1) whose weight is N(0, initializer_range) and
    whose bias is 0."""
    d, n, std = cfg.hidden_size, cfg.layers, cfg.initializer_range
    h, hk, dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    ke, kq, kk, kv, ko, km, kh, kg = jax.random.split(key, 8)
    layers = {
        "attn_norm": _scale(d, (n,)),
        "attn": {"q": _w(kq, (n, d, h * dh), std), "k": _w(kk, (n, d, hk * dh), std),
                 "v": _w(kv, (n, d, hk * dh), std),
                 "o": _w(ko, (n, h * dh, d), _out_std(cfg))},
        "attn_out_norm": _scale(d, (n,)),
        "mlp_norm": _scale(d, (n,)),
        "mlp": _swiglu_init(km, (n,), d, cfg.intermediate_size, cfg),
        "mlp_out_norm": _scale(d, (n,)),
    }
    return {"embed": {"table": std * jax.random.normal(
                ke, (cfg.vocab_size, d), jnp.float32)},
            "final_norm": _scale(d), "head": _w(kh, (d, cfg.vocab_size), std),
            "exit_gate": {**_w(kg, (d, 1), std), "b": jnp.zeros((1,), jnp.float32)},
            "dense": layers}


def deepseek_init(key, cfg: DecoderConfig):
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


def rope(x, theta: float, inv_freq=None, attention_factor=None):
    """Rotate the interleaved pairs (x_2i, x_2i+1) of the last axis by
    position * theta^(-2i/d), or by position * `inv_freq`[i] where a table
    (d / 2,) is given; `attention_factor` multiplies cos and sin (YaRN).
    x: (B, L, ..., d), positions 0..L-1."""
    L, d = x.shape[1], x.shape[-1]
    if inv_freq is None:
        inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    else:
        inv = jnp.asarray(inv_freq, jnp.float32)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((1, L) + (1,) * (x.ndim - 3) + (d // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if attention_factor is not None:
        cos, sin = cos * attention_factor, sin * attention_factor
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


def yarn_inv_freq(dim: int, theta: float, factor: float, original_max: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's (dim / 2,) rotation frequencies as `transformers` computes
    them (`_compute_yarn_parameters`, `truncate` on), whatever the sequence
    length: pairs that turn more than `beta_fast` times over `original_max`
    positions keep theta^(-2i/dim), pairs that turn less than `beta_slow`
    times are slowed `factor` times, a linear ramp over the pair index
    between."""
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def pair_turning(turns):  # the (fractional) pair that turns `turns` times
        return dim * math.log(original_max / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_turning(beta_fast)), 0)
    high = min(math.ceil(pair_turning(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return f / factor * (1.0 - keep) + f * keep


def _rope_table(section: dict, dim: int):
    """(theta, inv_freq or None, attention_factor or None) of one section
    of `rope_parameters`: `default` is plain RoPE, `yarn` the table above."""
    theta, kind = float(section["rope_theta"]), section.get("rope_type", "default")
    if kind == "default":
        return theta, None, None
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r}: the decoder turns by `default` "
                         "or `yarn`")
    inv = yarn_inv_freq(dim, theta, section["factor"],
                        section["original_max_position_embeddings"],
                        section["beta_fast"], section["beta_slow"])
    return theta, inv, section.get("attention_factor")


def gqa_apply(params, x, cfg, kind: str):
    """Grouped-query attention of one layer kind (its window, its position
    table), q and k normed by head where the layer has the norms (`mellum`;
    an `ouro` layer has none). x: (B, L, d)."""
    B, L, _ = x.shape
    h, hk, dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    dtype = cfg.compute_dtype
    with scope("qkv_proj"):
        q = linear(params["q"], x, dtype).reshape(B, L, h, dh)
        k = linear(params["k"], x, dtype).reshape(B, L, hk, dh)
        v = linear(params["v"], x, dtype).reshape(B, L, hk, dh)
    with scope("qk_norm_rope"):
        table = _rope_table(cfg.rope_of(kind), dh)

        def normed(t, name):
            return rms_norm(params[name], t, cfg.rms_norm_eps) if name in params else t

        q = rope(normed(q, "q_norm"), *table)
        k = rope(normed(k, "k_norm"), *table)
    out = flash_attention(q, k, v, causal=True, window=cfg.window_of(kind),
                          scale=dh ** -0.5)
    with scope("out_proj"):
        return linear(params["o"], out.reshape(B, L, h * dh), dtype)


def _shift(x, steps: int):
    """x (B, L, ...) moved `steps` positions later, zeros in front."""
    if not steps:
        return x
    pad = [(0, 0)] * x.ndim
    pad[1] = (steps, 0)
    return jnp.pad(x, pad)[:, :x.shape[1]]


def _causal_conv(params, x, product):
    """sum_j product(x moved taps - 1 - j later, w[j]) + b: a convolution
    over the sequence that pads on the left only. x: (B, L, H, dh)."""
    w = params["w"]
    taps = w.shape[0]
    y = sum(product(_shift(x, taps - 1 - j), w[j]) for j in range(taps))
    return y + params["b"].reshape(x.shape[2:])


def cca_apply(params, x, cfg: ZayaConfig):
    """Compressed convolutional attention with grouped keys. x: (B, L, d)."""
    B, L, _ = x.shape
    h, hk, dh = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    g, dtype, f32 = h // hk, cfg.compute_dtype, jnp.float32
    with scope("qkv_proj"):
        q0 = linear(params["q"], x, dtype).reshape(B, L, h, dh)
        k0 = linear(params["k"], x, dtype).reshape(B, L, hk, dh)
    with scope("conv_mix"):
        qk = jnp.concatenate([q0, k0], axis=2)
        qk = _causal_conv(params["conv_a"], qk.astype(f32),
                          lambda t, w: t * w.reshape(h + hk, dh)).astype(dtype)
        qk = _causal_conv(
            params["conv_b"], qk,
            lambda t, w: jnp.einsum("blhc,hcd->blhd", t, w.astype(dtype),
                                    preferred_element_type=f32))
        q0, k0 = q0.astype(f32), k0.astype(f32)
        q = qk[:, :, :h] + (q0 + jnp.repeat(k0, g, axis=2)) / 2
        k = qk[:, :, h:] + (jnp.mean(q0.reshape(B, L, hk, g, dh), axis=3) + k0) / 2
    with scope("qk_norm_rope"):
        def unit(t):  # sqrt(dh) t / |t| over a head's lanes
            return t * jax.lax.rsqrt(jnp.mean(jnp.square(t), axis=-1, keepdims=True))

        rot = cfg.rotary_dim

        def turned(t):
            return jnp.concatenate(
                [rope(t[..., :rot], cfg.rope_theta), t[..., rot:]], axis=-1)

        q = turned(unit(q)).astype(dtype)
        k = turned(unit(k) * params["tau"][:, None]).astype(dtype)
    with scope("value_shift"):
        v = jnp.concatenate([linear(params["v1"], x, dtype),
                             _shift(linear(params["v2"], x, dtype), 1)], axis=-1)
    out = flash_attention(q, k, v.reshape(B, L, hk, dh), causal=True,
                          scale=dh ** -0.5)
    with scope("out_proj"):
        return linear(params["o"], out.reshape(B, L, h * dh), dtype)


def _dense_f32(params, x):
    return jnp.matmul(x, params["w"], precision=jax.lax.Precision.HIGHEST) + params["b"]


def zaya_router_logits(params, x, r_prev, cfg: ZayaConfig):
    """The ZAYA1 router's state and scores, in float32. x: (N, d) the
    expert sublayer's normed input; r_prev: (N, router_hidden_size) the
    state of the layer before. Returns (logits (N, E), r (N, R))."""
    xn = rms_norm(params["norm"], x.astype(jnp.float32), cfg.rms_norm_eps)
    r = jnp.matmul(xn, params["reduce"]["w"], precision=jax.lax.Precision.HIGHEST)
    r = r + params["gamma"] * r_prev
    t = jax.nn.gelu(_dense_f32(params["fc1"], r))
    t = jax.nn.gelu(_dense_f32(params["fc2"], t))
    return _dense_f32(params["fc3"], t), r


def _scaled_residual_add(params, h, y):
    """(a * h + c) + y in float32, back in h's dtype."""
    with scope("residual_scale"):
        return (h.astype(jnp.float32) * params["a"] + params["c"] + y).astype(h.dtype)


def _zaya_layer(lp, carry, cfg: ZayaConfig):
    """One `zaya` layer on the scan's carry (h (B, L, d), r (B L, R))."""
    h, r = carry
    with scope("cca_attn"):
        y = cca_apply(lp["attn"], rms_norm(lp["attn_norm"], h, cfg.rms_norm_eps), cfg)
    h = _scaled_residual_add(lp["attn_res"], h, y)
    B, L, d = h.shape
    with scope("moe"):
        x = rms_norm(lp["mlp_norm"], h, cfg.rms_norm_eps).reshape(B * L, d)
        with scope("router"):
            logits, r = zaya_router_logits(lp["mlp"]["router"], x, r, cfg)
            routing = moe.route_softmax(logits, lp["mlp"]["bias"],
                                        cfg.num_experts_per_tok)
        y, aux = moe.moe_apply(lp["mlp"], x, routing, held=cfg.held)
    h = _scaled_residual_add(lp["mlp_res"], h, y.reshape(B, L, d))
    return (h, r), aux


def _mellum_layer(lp, h, cfg: MellumConfig, kind: str):
    with scope("gqa_attn"):
        h = h + gqa_apply(lp["attn"], rms_norm(lp["attn_norm"], h,
                                               cfg.rms_norm_eps), cfg, kind)
    B, L, d = h.shape
    with scope("moe"):
        x = rms_norm(lp["mlp_norm"], h, cfg.rms_norm_eps).reshape(B * L, d)
        with scope("router"):
            routing = moe.route_softmax(
                moe.router_logits(lp["mlp"], x), None, cfg.num_experts_per_tok,
                norm_topk=cfg.norm_topk_prob)
        y, aux = moe.moe_apply(lp["mlp"], x, routing, held=cfg.held)
        return h + y.reshape(B, L, d), aux


def _ouro_layer(lp, h, cfg: OuroConfig):
    """One `ouro` layer: each sublayer between an RMSNorm of its input and
    one of its output, the second under `sandwich_norm`."""
    eps = cfg.rms_norm_eps
    with scope("gqa_attn"):
        a = gqa_apply(lp["attn"], rms_norm(lp["attn_norm"], h, eps), cfg, FULL)
    with scope("sandwich_norm"):
        h = h + rms_norm(lp["attn_out_norm"], a, eps)
    with scope("dense_mlp"):
        m = moe.swiglu(lp["mlp"], rms_norm(lp["mlp_norm"], h, eps), cfg.compute_dtype)
    with scope("sandwich_norm"):
        return h + rms_norm(lp["mlp_out_norm"], m, eps), None


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
        with scope("router"):
            routing = moe.route(
                lp["mlp"], x, top_k=cfg.num_experts_per_tok,
                scaling=cfg.routed_scaling_factor, norm_topk=cfg.norm_topk_prob)
        y, aux = moe.moe_apply(lp["mlp"], x, routing, held=cfg.held)
        return h + y.reshape(B, L, d), aux


def _checkpointed_layer(layer):
    """`layer` (layer params, carry) -> (carry, aux) as the scans run it,
    (carry, layer params) -> (carry, aux), under the layer's
    `jax.checkpoint`. It keeps the causal kernel's `out` and `lse` (a
    residual stream's width twice over a layer, and what the backward
    kernel reads besides q, k, v) and builds everything else again in the
    backward pass, so the core's forward runs once a step. Where the core
    takes the XLA arm the layer holds no such name and is recomputed
    whole. The carry is whatever the family's layer hands on: `h`, or
    `(h, r)` with the router's state."""
    return jax.checkpoint(lambda carry, lp: layer(lp, carry),
                          policy=core_checkpoint_policy())


def _stack(layer, carry, layers):
    return jax.lax.scan(_checkpointed_layer(layer), carry, layers)


def _deepseek_layers(params, h, cfg: DecoderConfig):
    aux = {}
    if "dense" in params:
        h, _ = _stack(lambda lp, c: _layer(lp, c, cfg, False), h, params["dense"])
    if "moe" in params:
        h, aux = _stack(lambda lp, c: _layer(lp, c, cfg, True), h, params["moe"])
    return h, aux


def _zaya_layers(params, h, cfg: ZayaConfig):
    B, L, _ = h.shape
    r = jnp.zeros((B * L, cfg.router_hidden_size), jnp.float32)
    (h, _), aux = _stack(lambda lp, c: _zaya_layer(lp, c, cfg), (h, r),
                         params["moe"])
    return h, aux


def _runs(kinds):
    """[(kind, start, stop)] of the runs of one kind in `kinds`."""
    starts = [i for i, kind in enumerate(kinds) if i == 0 or kind != kinds[i - 1]]
    return [(kinds[a], a, b) for a, b in zip(starts, starts[1:] + [len(kinds)])]


def _mellum_layers(params, h, cfg: MellumConfig):
    """The layers in the published order with the window static at each
    call of the core: a scan over whole periods of `layer_types`, whose
    body scans each run of one kind (a run of one layer is called
    directly), every layer under `_checkpointed_layer`. The aux comes back
    a row a layer, in that order."""
    period, n = cfg.period, cfg.num_hidden_layers

    def at(tree, index):
        return jax.tree_util.tree_map(lambda t: t[index], tree)

    def one_period(h, pp):
        rows = []
        for kind, a, b in _runs(period):
            layer = functools.partial(_mellum_layer, cfg=cfg, kind=kind)
            if b - a > 1:
                h, aux = _stack(layer, h, at(pp, slice(a, b)))
            else:
                h, aux = _checkpointed_layer(layer)(h, at(pp, a))
                aux = at(aux, None)
            rows.append(aux)
        return h, jax.tree_util.tree_map(lambda *ts: jnp.concatenate(ts), *rows)

    periods = jax.tree_util.tree_map(
        lambda t: t.reshape((n // len(period), len(period)) + t.shape[1:]),
        params["moe"])
    h, aux = jax.lax.scan(one_period, h, periods)
    return h, jax.tree_util.tree_map(lambda t: t.reshape((n,) + t.shape[2:]), aux)


def exit_gate_logits(params, h):
    """z = h w_g + b_g of each token, in float32; lambda = sigmoid(z) is the
    chance of leaving after this pass. h: (B, L, d), a pass's normed
    state."""
    z = jnp.matmul(h.astype(jnp.float32), params["w"],
                   precision=jax.lax.Precision.HIGHEST) + params["b"]
    return z[..., 0]


def _ouro_layers(params, h, cfg: OuroConfig):
    """The stack run `total_ut_steps` times over the same stacked
    parameters: a Python loop of passes, each a scan over the layers under
    `_checkpointed_layer`, each followed by the final norm and the exit
    gate. The normed state is what the next pass reads. Returns (the T
    normed states, a list of (B, L) arrays, each read by its own pass's
    head; {"gate_logits": (T, B, L) float32})."""
    layer = functools.partial(_ouro_layer, cfg=cfg)
    states, gates = [], []
    for _ in range(cfg.total_ut_steps):
        h, _ = _stack(layer, h, params["dense"])
        with scope("exit_gate"):
            h = rms_norm(params["final_norm"], h, cfg.rms_norm_eps)
            gates.append(exit_gate_logits(params["exit_gate"], h))
        states.append(h)
    return states, {"gate_logits": jnp.stack(gates)}


#: what differs between the families, by the configuration's class: (the
#: init, the layer stacks on the embedded tokens). The class itself names
#: its `model_type`, its key for the router's width and the core's
#: `qk_head_dim` / `v_head_dim`
_FAMILY = {DecoderConfig: (deepseek_init, _deepseek_layers),
           ZayaConfig: (zaya_init, _zaya_layers),
           MellumConfig: (mellum_init, _mellum_layers),
           OuroConfig: (ouro_init, _ouro_layers)}
#: model_type -> the configuration's class (train_lm.py `config_from_file`)
FAMILIES = {cls.model_type: cls for cls in _FAMILY}


def decoder_init(key, cfg):
    """The family's parameter tree: `deepseek_init`'s, `zaya_init`'s,
    `mellum_init`'s or `ouro_init`'s."""
    return _FAMILY[type(cfg)][0](key, cfg)


def decoder_apply(params, cfg, tokens):
    """tokens (B, L) int -> (hidden (B, L, d) after the final norm, in the
    compute dtype; aux {"load": (n_moe, E), "picks": (n_moe, B*L, top_k),
    "rows_walked": (n_moe,)}, empty without MoE layers). A family with no
    expert layer has none of these: `ouro` returns a list of its T passes'
    normed states in the place of `hidden` and {"gate_logits": (T, B, L)}."""
    with scope("lm_embed"):
        # rows from the float32 table, so that the table's gradient adds
        # up in float32 however often a token repeats
        h = embedding(params["embed"], tokens).astype(cfg.compute_dtype)
    # what the layer scans do themselves (a layer's slice of the stacked
    # parameters, its gradient's write-back, the carried residual stream)
    with scope("decoder_layers"):
        h, aux = _FAMILY[type(cfg)][1](params, h, cfg)
    if "gate_logits" in aux:
        return h, aux  # a looped stack: each pass ended in the final norm
    with scope("lm_head_loss"):
        h = rms_norm(params["final_norm"], h, cfg.rms_norm_eps)
    return h, aux
