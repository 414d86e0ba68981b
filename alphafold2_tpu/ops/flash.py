"""Blockwise (flash-style) exact attention with bounded memory.

The reference materializes the full (i, j) attention matrix per head
(reference alphafold2_pytorch/alphafold2.py:152-174). At the north-star
scale (crop 384 -> 1152x1152 pair grid, the grid axis folded into batch for
axial attention) that matrix is tens of GB per layer — it cannot exist on a
16G chip. This module computes the same softmax(QK^T)V exactly but tiled:
query tiles stream over K/V blocks accumulating running-max / sum statistics
in float32 (the FlashAttention recurrence, shared with ring attention in
parallel/sequence.py and the Pallas block-sparse kernel in
ops/sparse_kernel.py). Peak live memory is one (q_tile, kv_block) logit tile
instead of the full matrix.

Each tile is wrapped in `jax.checkpoint`, so the backward pass recomputes
tile activations instead of storing them — the memory bound holds for
training. Tiles stay large and static-shaped so XLA maps them onto the MXU;
this is the portable (CPU-testable) sibling of a Pallas dense flash kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from alphafold2_tpu.telemetry.profiling import scope

_NEG_INF = float("-inf")


def stream_block(q, k_blk, v_blk, bias_blk, m, l, acc, scale,
                 bias2d_blk=None):
    """One flash-attention accumulation step against a K/V block.

    q: (b, nq, h, d); k_blk/v_blk: (b, nk, h, d); bias_blk: (b, nk) additive
    (-inf for masked keys). Running stats m, l: (b, h, nq); acc: (b, h, nq, d).
    bias2d_blk: optional (b, h, nq, nk) full pair-bias block added to the
    logits (the XLA twin of the fused kernel's streamed 2-D bias tiles);
    bias_blk may be None when it is given (fold masks into the 2-D bias).
    The (b, h, nq, nk) score and probability tiles are float32, like the
    running stats and the accumulator; the AV dot casts p to v's dtype.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk).astype(jnp.float32) * scale
    if bias_blk is not None:
        s = s + bias_blk[:, None, None, :].astype(jnp.float32)
    if bias2d_blk is not None:
        s = s + bias2d_blk.astype(jnp.float32)

    # No gradient through the running max (jax.nn.softmax does the same):
    # every consumer combines (m, l, acc) shift-invariantly (acc / l,
    # m + log l), so the partial w.r.t. m is identically zero and dropping
    # it is exact. It is also what keeps the backward finite on the TPU:
    # with bf16 operands and the K/V blocks under `lax.scan`, every dq and
    # dk came back NaN on a v5e (jax 0.9.0; finite in f32, with the blocks
    # unrolled, or with f32 logits straight from the dot — PERF.md
    # bring-up). reduce-max's gradient divides by the count of positions
    # EQUAL to the max; a bf16-rounded max compared against logits XLA
    # kept at higher precision matches nowhere, and 0/0 is the NaN.
    m_new = jax.lax.stop_gradient(
        jnp.maximum(m, jnp.max(s, axis=-1).astype(jnp.float32)))
    # alpha/p guards: -inf - -inf = nan. The exp ARGUMENT must be sanitized
    # too, not just the result: exp(nan) in the unselected where-branch has a
    # nan primal, and exp's vjp multiplies even a zero cotangent by it
    # (0 * nan = nan), poisoning dq/dk for fully-masked rows.
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    alpha = jnp.where(
        jnp.isneginf(m), 0.0, jnp.exp(jnp.where(jnp.isneginf(m), 0.0, m) - m_safe)
    )
    p = jnp.where(
        jnp.isneginf(s),
        0.0,
        jnp.exp(jnp.where(jnp.isneginf(s), 0.0, s) - m_safe[..., None]),
    )
    l_new = l * alpha + jnp.sum(p, axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p.astype(v_blk.dtype), v_blk
    ).astype(jnp.float32)
    return m_new, l_new, acc_new


def merge_lse(out_a, lse_a, out_b, lse_b):
    """Log-space merge of two NORMALIZED partial softmax results.

    The hop interface of kernel-path ring attention
    (parallel/sequence.py): each hop produces its block's normalized
    output plus the log-sum-exp of its logits (ops/flash_kernel.py
    `flash_attention_lse`), and blocks combine associatively:

        new_out = (e^lse_a * out_a + e^lse_b * out_b) / (e^lse_a + e^lse_b)
        new_lse = log(e^lse_a + e^lse_b)

    computed with the usual running-max stabilization. Zero-mass blocks
    (a fully-masked hop) must carry lse = -inf so they weigh ZERO — the
    kernel's +inf zero-mass convention is flipped before merging
    (parallel/sequence.py hop()). Both-empty rows return (0, -inf).

    out_*: (..., d) float32; lse_*: (...) float32. Returns (out, lse).
    """
    m = jnp.maximum(lse_a, lse_b)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)  # both-empty rows
    w_a = jnp.exp(lse_a - m_safe)
    w_b = jnp.exp(lse_b - m_safe)
    tot = w_a + w_b
    safe_tot = jnp.where(tot > 0, tot, 1.0)
    out = jnp.where(
        (tot > 0)[..., None],
        (out_a * w_a[..., None] + out_b * w_b[..., None]) / safe_tot[..., None],
        0.0,
    )
    lse = jnp.where(tot > 0, m_safe + jnp.log(safe_tot), _NEG_INF)
    return out, lse


def _largest_divisor_leq(n: int, cap: int) -> int:
    cap = max(1, min(n, cap))
    for c in range(cap, 0, -1):
        if n % c == 0:
            return c
    return 1


def _tile_attention(q, k, v, bias, scale, kv_block):
    """Exact attention for one query tile, streaming K/V blocks."""
    b, nq, h, dh = q.shape
    j = k.shape[1]
    m0 = jnp.full((b, h, nq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, nq), jnp.float32)
    acc0 = jnp.zeros((b, h, nq, dh), jnp.float32)

    if kv_block is None or j <= kv_block:
        m, l, acc = stream_block(q, k, v, bias, m0, l0, acc0, scale)
    else:
        pad = (-j) % kv_block
        if pad:
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            bias = jnp.pad(bias, ((0, 0), (0, pad)), constant_values=_NEG_INF)
        nb = (j + pad) // kv_block
        ks = k.reshape(b, nb, kv_block, h, dh).transpose(1, 0, 2, 3, 4)
        vs = v.reshape(b, nb, kv_block, h, dh).transpose(1, 0, 2, 3, 4)
        bs = bias.reshape(b, nb, kv_block).transpose(1, 0, 2)

        def body(carry, blk):
            mm, ll, aa = carry
            kb, vb, bb = blk
            return stream_block(q, kb, vb, bb, mm, ll, aa, scale), None

        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (ks, vs, bs))

    out = acc / jnp.where(l > 0, l, 1.0)[..., None]  # zeros for all-masked q
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def blockwise_attention(
    q,
    k,
    v,
    key_bias=None,
    *,
    scale=None,
    tile_elems: int = 1 << 25,
    kv_block: int = 2048,
    remat: bool = True,
):
    """Exact softmax(QK^T * scale + bias)V with bounded-memory tiling.

    Args:
      q: (B, i, h, dh) queries — B may be a huge folded-batch axis (axial
        attention) or 1 with huge i (flat cross-attention); tiling adapts.
      k, v: (B, j, h, dh).
      key_bias: (B, j) additive float32, 0 for valid keys / -inf for masked
        (key-side masking only, matching the reference's key-padding
        semantics, alphafold2.py:156-161). Query-side masking is
        intentionally absent: masked query rows produce finite values that
        downstream masking discards — the same contract as the dense path,
        which gives those rows uniform-attention garbage instead.
      tile_elems: target max elements per (batch*h*q*kv) logit tile
        (default 2^25 = 128 MB in f32).
      kv_block: stream K/V in blocks of this length when j exceeds it.
        Both stay function arguments: Alphafold2Config's
        attn_flash_tile_elems / attn_flash_kv_block reach them, and
        scripts/micro_attn_core.py sweeps tile_elems (ROADMAP S5 settles
        the config fields with the cell's re-measurement).
      remat: jax.checkpoint each tile so backward recomputes instead of
        storing tile activations.

    Returns: (B, i, h, dh) in q.dtype. Fully-masked query rows return zeros.
    """
    B, i, h, dh = q.shape
    j = k.shape[1]
    scale = dh ** -0.5 if scale is None else scale
    if key_bias is None:
        key_bias = jnp.zeros((B, j), jnp.float32)

    j_eff = min(j, kv_block) if kv_block else j
    per_q_row = max(1, h * j_eff)
    qb = max(1, min(i, tile_elems // per_q_row))
    bb = _largest_divisor_leq(B, max(1, tile_elems // (per_q_row * min(i, qb))))
    kvb = kv_block if (kv_block and j > kv_block) else None

    def tile(qt, kt, vt, bt):
        return _tile_attention(qt, kt, vt, bt, scale, kvb)

    if remat:
        tile = jax.checkpoint(tile)

    if bb == B and qb >= i:
        return tile(q, k, v, key_bias)

    pad_i = (-i) % qb
    if pad_i:
        q = jnp.pad(q, ((0, 0), (0, pad_i), (0, 0), (0, 0)))
    nq = (i + pad_i) // qb

    def batch_chunk(args):
        qc, kc, vc, bc = args  # (bb, i_p, h, dh), (bb, j, h, dh), (bb, j)
        if nq == 1:
            return tile(qc, kc, vc, bc)
        qs = qc.reshape(bb, nq, qb, h, dh).transpose(1, 0, 2, 3, 4)
        out = jax.lax.map(lambda qt: tile(qt, kc, vc, bc), qs)
        return out.transpose(1, 0, 2, 3, 4).reshape(bb, nq * qb, h, dh)

    if bb == B:
        out = batch_chunk((q, k, v, key_bias))
    else:
        nb = B // bb

        def resh(t):
            return t.reshape((nb, bb) + t.shape[1:])

        out = jax.lax.map(batch_chunk, (resh(q), resh(k), resh(v), resh(key_bias)))
        out = out.reshape((B, nq * qb, h, dh))

    return out[:, :i] if pad_i else out


def causal_blockwise_attention(q, k, v, *, scale=None, block: int = 1024,
                               remat: bool = True, window: int | None = None):
    """Exact causal self-attention, streamed: softmax(QK^T * scale) V under
    the lower-triangular mask, with a value head size of its own.

    q, k: (B, n, h, dh); v: (B, n, h, dv). Queries walk in tiles of
    `block`; tile t streams the key blocks below the diagonal that need no
    mask (`stream_block` under `lax.scan`) and then, each under its mask,
    the blocks that do: the one the diagonal crosses and, with a `window`
    (query i sees keys j with i - window < j <= i), those the band's lower
    edge crosses. Blocks above the diagonal or wholly behind the band are
    never built: the loop over tiles is a Python loop, so each tile's trip
    count is static. Returns (B, n, h, dv) in q.dtype."""
    from alphafold2_tpu.ops import flash_kernel

    B, n, h, dh = q.shape
    dv = v.shape[-1]
    scale = dh ** -0.5 if scale is None else scale
    block = min(block, n)
    pad = (-n) % block
    if pad:  # a padded key lies past every real query
        q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for t in (q, k, v))
    nb = (n + pad) // block
    at = jnp.arange(block)

    def mask(d):
        """The additive mask of the block d blocks under the diagonal."""
        apart = at[:, None] - at[None, :] + d * block  # query - key
        seen = apart >= 0
        if window is not None:
            seen &= apart < window
        return jnp.where(seen, 0.0, _NEG_INF)[None, None]

    # in blocks under the diagonal, the band's geometry as the kernel's
    # schedule has it: the blocks that the band's lower edge crosses take
    # a mask (they are the farthest a tile reaches), those nearer none
    crossed = flash_kernel.masked_distances(window, block)[:0:-1]
    reach = nb if window is None else flash_kernel.window_blocks(window, block)
    edge = min(crossed, default=reach + 1)

    def cut(t):  # (nb, B, block, h, d)
        return t.reshape(B, nb, block, h, t.shape[-1]).transpose(1, 0, 2, 3, 4)

    ks, vs = cut(k), cut(v)

    def tile(qt, k_plain, v_plain, masked):
        carry = (jnp.full((B, h, block), _NEG_INF, jnp.float32),
                 jnp.zeros((B, h, block), jnp.float32),
                 jnp.zeros((B, h, block, dv), jnp.float32))

        def body(c, blk):
            return stream_block(qt, blk[0], blk[1], None, *c, scale), None

        if k_plain.shape[0]:
            carry, _ = jax.lax.scan(body, carry, (k_plain, v_plain))
        for k_blk, v_blk, bias in masked:
            carry = stream_block(qt, k_blk, v_blk, None, *carry, scale,
                                 bias2d_blk=bias)
        _, l, acc = carry
        return jnp.transpose(acc / l[..., None], (0, 2, 1, 3)).astype(q.dtype)

    if remat:
        tile = jax.checkpoint(tile)
    out = []
    for t in range(nb):
        plain = slice(t - min(t, edge - 1), t)
        masked = [d for d in crossed if d <= t] + [0]
        out.append(tile(q[:, t * block:(t + 1) * block], ks[plain], vs[plain],
                        [(ks[t - d], vs[t - d], mask(d)) for d in masked]))
    out = jnp.concatenate(out, axis=1) if nb > 1 else out[0]
    return out[:, :n] if pad else out


def _causal_attention_arms(q, k, v, key_bias, scale, use_kernel, kernel_qb,
                           kernel_kb, window, blockwise_kwargs):
    from alphafold2_tpu.ops import dispatch, flash_kernel

    if key_bias is not None:
        raise ValueError("causal flash_attention takes no key bias: the "
                         "mask is the causal one, or its band under `window`")
    if window is not None and window < 1:
        raise ValueError(f"causal flash_attention: a window of {window} keys "
                         "holds no key; every query sees its own")
    i, dh = q.shape[1], q.shape[-1]
    j, dv = k.shape[1], v.shape[-1]
    # grouped keys: each key head serves `group` query heads in a row.
    # Either arm takes one head count, so k and v are repeated here (their
    # gradients add up over a group through the repeat's transpose)
    group, rest = divmod(q.shape[2], k.shape[2])
    if rest or v.shape[2] != k.shape[2]:
        raise ValueError(f"causal flash_attention: {k.shape[2]} key and "
                         f"{v.shape[2]} value heads do not serve {q.shape[2]} "
                         "query heads")
    if group > 1:
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    arm = dispatch.resolve("flash_attention", request=use_kernel, i=i, j=j,
                           dh=dh, dv=dv, causal=True)
    if arm == dispatch.ARM_PALLAS_TPU:
        return flash_kernel.flash_attention_causal_bnhd(
            q, k, v, scale, qb=kernel_qb, kb=kernel_kb, window=window)
    kwargs = {"window": window}
    if "remat" in blockwise_kwargs:
        kwargs["remat"] = blockwise_kwargs["remat"]
    if "kv_block" in blockwise_kwargs:
        kwargs["block"] = blockwise_kwargs["kv_block"]
    return causal_blockwise_attention(q, k, v, scale=scale, **kwargs)


def apply_output_gate(out, gate):
    """The UNFUSED sigmoid output-gate epilogue: sigmoid in f32 on the
    f32 output, one cast at the end — the exact math the fused kernel's
    finish step runs in VMEM (ops/flash_kernel.py), so kernel-on and
    kernel-off arms of a gated model differ only in rounding. out / gate:
    (..., dh) matching shapes; gate holds pre-sigmoid logits."""
    return (
        out.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))
    ).astype(out.dtype)


def streamed_fused_attention(q, k, v, key_bias, pair_bias, gate, scale,
                             kv_block: int = 2048, remat: bool = True):
    """XLA twin of the fused-epilogue kernel: 2-D pair bias + output gate.

    q: (B, i, h, dh); k, v: (B, j, h, dh); pair_bias: (B, h, i, j) f32
    additive; key_bias: optional (B, j) mask bias folded in; gate:
    optional (B, i, h, dh) pre-sigmoid logits. K/V and bias stream in
    `kv_block` chunks with the flash recurrence, so the live logit tile is
    (B, h, i, kv_block) — bounded along j only (the 2-D bias itself is a
    caller-materialized (B, h, i, j) input, so there is no q-tiling win to
    chase here; the Pallas kernel is the production TPU path).
    Exact at f32; the parity oracle for the fused kernel's interpret-mode
    tests."""
    B, i, h, dh = q.shape
    j = k.shape[1]
    bias = pair_bias.astype(jnp.float32)
    if key_bias is not None:
        bias = bias + key_bias[:, None, None, :].astype(jnp.float32)

    def run(q, k, v, bias):
        m0 = jnp.full((B, h, i), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, h, i), jnp.float32)
        acc0 = jnp.zeros((B, h, i, dh), jnp.float32)
        if j <= kv_block:
            m, l, acc = stream_block(q, k, v, None, m0, l0, acc0, scale,
                                     bias2d_blk=bias)
        else:
            pad = (-j) % kv_block
            if pad:
                k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                bias = jnp.pad(bias, ((0, 0), (0, 0), (0, 0), (0, pad)),
                               constant_values=_NEG_INF)
            nb = (j + pad) // kv_block
            ks = k.reshape(B, nb, kv_block, h, dh).transpose(1, 0, 2, 3, 4)
            vs = v.reshape(B, nb, kv_block, h, dh).transpose(1, 0, 2, 3, 4)
            bs = bias.reshape(B, h, i, nb, kv_block).transpose(3, 0, 1, 2, 4)

            def body(carry, blk):
                mm, ll, aa = carry
                kb, vb, bb = blk
                return stream_block(q, kb, vb, None, mm, ll, aa, scale,
                                    bias2d_blk=bb), None

            (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (ks, vs, bs))
        out = acc / jnp.where(l > 0, l, 1.0)[..., None]
        return jnp.transpose(out, (0, 2, 1, 3))  # (B, i, h, dh) f32

    if remat:
        run = jax.checkpoint(run)
    out = run(q, k, v, bias)
    if gate is not None:
        out = out * jax.nn.sigmoid(gate.astype(jnp.float32))
    return out.astype(q.dtype)


def hop_attention_lse(qf, kf, vf, bias, scale):
    """One ring hop's normalized (out, lse) through the Pallas kernel —
    the `merge_lse` op's kernel arm, wrapped here so
    parallel/sequence.py never imports a kernel module directly (the
    dispatch lint's import monopoly).

    qf/kf/vf: (BH, n, dh) folded layout; bias: (BH, nk) additive f32.
    The kernel marks zero-mass rows with +inf lse (its backward
    convention); for cross-hop combination zero mass must weigh ZERO —
    flipped to -inf here (the `merge_lse` contract). Returns
    (out f32, lse f32)."""
    from alphafold2_tpu.ops import flash_kernel

    out_h, lse_h = flash_kernel.flash_attention_lse(qf, kf, vf, bias, scale)
    lse_h = jnp.where(jnp.isposinf(lse_h), _NEG_INF, lse_h)
    return out_h.astype(jnp.float32), lse_h


def flash_attention(q, k, v, key_bias=None, *, pair_bias=None, gate=None,
                    scale=None, use_kernel="auto", causal=False, window=None,
                    kernel_qb=None, kernel_kb=None, **blockwise_kwargs):
    """Exact attention: fused Pallas kernel on TPU, XLA blockwise otherwise.

    Same contract as `blockwise_attention` (q (B, i, h, dh); k, v
    (B, j, h, dh); key-side (B, j) additive bias). use_kernel: True forces
    the kernel (interpret mode off-TPU — for tests), False forces XLA
    streaming, "auto" asks ops/dispatch.py `resolve`, which takes the
    kernel on TPU for supported shapes (ops/flash_kernel.py `supported`)
    from the measured crossover in key length up — the pair stream's
    axial passes (i = j = 1152), where its whole-row form keeps the
    logit tile in VMEM (PERF.md section 5); the short crosses below it
    were not measured to win and stay on XLA streaming. The kernel picks
    its own form and blocks from (i, j, h, dh) (ops/flash_kernel.py
    `flash_attention_bnhd`). kernel_qb/kernel_kb force its streaming
    form at those query/key blocks (kernel path only). They stay
    function arguments because tests force small blocks so that
    interpret mode walks a multi-block schedule, and
    scripts/micro_attn_core.py sweeps them; no config field reaches them.

    Fused epilogue: `pair_bias` (B, h, i, j) f32 full 2-D additive bias
    tiles and/or `gate` (B, i, h, dh) pre-sigmoid output-gate logits.
    On the kernel path both fuse INTO the Pallas kernel
    (ops/flash_kernel.py `flash_attention_fused` — the bias-add and the
    gate-multiply stop costing separate HBM logit/output passes); off
    kernel, the gate applies as an exact epilogue over the blockwise
    result and pair-bias streams through `streamed_fused_attention`.

    `causal=True` is self-attention (i = j) under the lower-triangular
    mask, with `v`'s head size free of q's and k's and, where k and
    v have fewer heads than q, each key head serving the q.shape[2] /
    k.shape[2] query heads that follow one another (ops/flash_kernel.py
    `flash_attention_causal_bnhd`, where kernel_qb / kernel_kb force the
    block and the sub-tile of a step; `causal_blockwise_attention` off the
    kernel): only tiles on or below the diagonal. With `window` (a static
    whole number of keys, causal calls only) query i sees keys j with
    i - window < j <= i, its own the last of them (sliding-window layers;
    `transformers`' convention): either arm then builds only the tiles of
    that band, and the call's device operations carry the name
    `attn_core_window`. No bias, no gate.
    """
    if window is not None and not causal:
        raise ValueError("flash_attention: `window` is the causal band's; "
                         "it needs causal=True")
    # whichever arm runs, its device operations carry the one name
    with scope("attn_core" if window is None else "attn_core_window"):
        if causal:
            if pair_bias is not None or gate is not None:
                raise ValueError("causal flash_attention takes no pair "
                                 "bias and no gate")
            scale = q.shape[-1] ** -0.5 if scale is None else scale
            return _causal_attention_arms(
                q, k, v, key_bias, scale, use_kernel, kernel_qb, kernel_kb,
                window, blockwise_kwargs)
        return _flash_attention_arms(
            q, k, v, key_bias, pair_bias=pair_bias, gate=gate, scale=scale,
            use_kernel=use_kernel, kernel_qb=kernel_qb, kernel_kb=kernel_kb,
            **blockwise_kwargs,
        )


def _flash_attention_arms(q, k, v, key_bias, *, pair_bias, gate, scale,
                          use_kernel, kernel_qb, kernel_kb,
                          **blockwise_kwargs):
    from alphafold2_tpu.ops import dispatch, flash_kernel

    B, i, h, dh = q.shape
    j = k.shape[1]
    scale = dh ** -0.5 if scale is None else scale
    fused = pair_bias is not None or gate is not None

    def takes_kernel(op):
        return dispatch.resolve(
            op, request=use_kernel, i=i, j=j, dh=dh
        ) == dispatch.ARM_PALLAS_TPU

    if fused and takes_kernel("fused_attention"):

        def fold(t):
            return t.transpose(0, 2, 1, 3).reshape(B * h, t.shape[1], dh)

        if pair_bias is not None:
            bias = pair_bias.astype(jnp.float32)
            if key_bias is not None:
                bias = bias + jnp.broadcast_to(
                    key_bias, (B, j)
                ).astype(jnp.float32)[:, None, None, :]
            bias = jnp.broadcast_to(bias, (B, h, i, j)).reshape(B * h, i, j)
        else:
            bias = (
                jnp.zeros((B, j), jnp.float32)
                if key_bias is None
                else jnp.broadcast_to(key_bias, (B, j)).astype(jnp.float32)
            )
            bias = jnp.repeat(bias, h, axis=0)
        gate_folded = fold(gate) if gate is not None else None
        out = flash_kernel.flash_attention_fused(
            fold(q), fold(k), fold(v), bias, scale,
            gate=gate_folded, qb=kernel_qb, kb=kernel_kb,
        )
        return out.reshape(B, h, i, dh).transpose(0, 2, 1, 3)

    if pair_bias is not None:
        # XLA twin of the 2-D-bias mode: j-streamed, exact at f32;
        # tile_elems is structurally inapplicable (the 2-D bias is a
        # caller-materialized (B, h, i, j) input, so there is no
        # q-tiling win — see streamed_fused_attention).
        return streamed_fused_attention(
            q, k, v, key_bias, pair_bias, gate, scale,
            kv_block=blockwise_kwargs.get("kv_block", 2048),
        )
    if gate is not None:
        # gate-only: the plain blockwise path plus the exact epilogue
        out = flash_attention(
            q, k, v, key_bias, scale=scale, use_kernel=False,
            **blockwise_kwargs,
        )
        return apply_output_gate(out, gate)

    if takes_kernel("flash_attention"):
        bias = (
            jnp.zeros((B, j), jnp.float32)
            if key_bias is None
            else jnp.broadcast_to(key_bias, (B, j)).astype(jnp.float32)
        )
        # the kernel takes the model's layout and picks its own form
        # (whole-row or streaming) from (i, j, h, dh)
        return flash_kernel.flash_attention_bnhd(
            q, k, v, bias, scale, qb=kernel_qb, kb=kernel_kb,
        )

    return blockwise_attention(
        q, k, v, key_bias, scale=scale, **blockwise_kwargs
    )


def causal_kernel_plan(n: int, h: int, dh: int, dv: int, dtype,
                       window: int | None = None) -> dict | None:
    """What the causal kernel makes of self-attention over n positions
    with h heads of dh (q, k) and dv (v): heads a grid step, block,
    sub-tile, grid steps a (batch, head group) row (`tiles`: those on or
    below the diagonal, under a `window` those of its band only, beside
    `tiles_triangle`, the triangle's count) and planned VMEM; None where
    the kernel does not take the shape. For a trainer's start-up log
    (train_lm.py)."""
    from alphafold2_tpu.ops import flash_kernel

    itemsize = jnp.dtype(dtype).itemsize
    plan = flash_kernel.causal_plan(n, h, dh, dv, itemsize, window=window)
    if plan is None:
        return None
    triangle = flash_kernel.causal_plan(n, h, dh, dv, itemsize).tiles
    return {**plan._asdict(), "tiles_triangle": triangle}


def core_checkpoint_policy():
    """The `jax.checkpoint` policy that keeps an attention kernel's two
    results (`flash_kernel.SAVED_NAMES`: `out` and `lse`, what its backward
    reads besides q, k, v) and recomputes everything else, so the kernel's
    forward is not run again for the backward pass: the causal form's under
    a decoder layer's checkpoint (models/decoder.py), the whole-row form's
    under a batch chunk's (ops/attention.py). With the XLA arm, or the
    kernel's streaming form, the checkpointed function holds no such name
    and is recomputed whole."""
    from alphafold2_tpu.ops import flash_kernel

    return jax.checkpoint_policies.save_only_these_names(
        *flash_kernel.SAVED_NAMES)


def _saved_bytes(rows: int, width: int, heads: int, dtype) -> dict:
    """{name: bytes} of a kernel's `out` (rows, width) in the operands'
    dtype and its `lse`, a float32 a (row, head)."""
    from alphafold2_tpu.ops import flash_kernel

    return dict(zip(flash_kernel.SAVED_NAMES,
                    (rows * width * jnp.dtype(dtype).itemsize, rows * heads * 4)))


def causal_saved_bytes(batch: int, n: int, h: int, dh: int, dv: int,
                       dtype) -> dict:
    """{name: bytes} of what a `jax.checkpoint` under
    `core_checkpoint_policy` (a layer of models/decoder.py) keeps of one
    causal core over `batch` sequences: where the kernel is the arm
    this host resolves for the shape, its `out` (batch, n padded to the
    block, h * dv) in the operands' dtype and its `lse`, a float32 a
    (sequence, head, position); with the XLA arm there are no such names
    and nothing is kept. For a trainer's start-up log (train_lm.py)."""
    from alphafold2_tpu.ops import dispatch, flash_kernel

    if dispatch._resolve("flash_attention", i=n, j=n, dh=dh, dv=dv,
                         causal=True) != dispatch.ARM_PALLAS_TPU:
        return {}
    qb = flash_kernel.causal_plan(n, h, dh, dv, jnp.dtype(dtype).itemsize).qb
    return _saved_bytes(batch * -(-n // qb) * qb, h * dv, h, dtype)


def rows_saved_bytes(batch: int, i: int, j: int, h: int, dh: int,
                     dtype) -> dict:
    """{name: bytes} of what the batch chunks' checkpoints (ops/attention.py
    `_batch_chunked_attention`) keep of one chunked attention pass over
    `batch` folded rows of i queries and j keys: where this host resolves
    the kernel arm for the shape AND the kernel takes it in its whole-row
    form, `out` (batch, i padded to 128, h * dh) and `lse` (batch, h, i
    padded); else {} (the XLA arm and the streaming form carry no name, the
    chunk is recomputed whole). For a trainer's start-up log
    (train_end2end.py)."""
    from alphafold2_tpu.ops import dispatch, flash_kernel

    if (dispatch._resolve("flash_attention", i=i, j=j, dh=dh)
            != dispatch.ARM_PALLAS_TPU
            or flash_kernel.rows_plan(i, j, h, dh,
                                      jnp.dtype(dtype).itemsize) is None):
        return {}
    return _saved_bytes(batch * -(-i // 128) * 128, h * dh, h, dtype)
