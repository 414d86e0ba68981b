"""Reversible dual-stream trunk: O(1) activation memory in depth.

TPU-native re-design of the reference's RevNet machinery
(reference alphafold2_pytorch/reversible.py). The reference implements
reversibility with a hand-written `torch.autograd.Function` that walks an
nn.ModuleList backwards, reconstructing activations block by block and
replaying captured RNG state so dropout matches on recompute
(reference reversible.py:266-292, 26-56). Here the whole trunk is ONE
`jax.custom_vjp` wrapping a `lax.scan` over stacked per-layer parameters:

  * forward: scan the layer body over the depth axis, saving only the FINAL
    (seq, msa) channel-halved state — true O(1) activation memory, and a
    single compiled layer body regardless of depth;
  * backward: reverse scan that inverts each layer (x2 = y2 - g(y1), ...)
    and accumulates parameter cotangents via per-block `jax.vjp`;
  * dropout determinism is free: op keys are `fold_in(rng, layer)` splits,
    re-derived identically in the backward pass (no RNG state capture).

Semantics match the reference exactly:
  * both streams are channel-doubled on entry and the two halves averaged on
    exit (reference reversible.py:319, 327);
  * each trunk layer is a self-attention block (f=seq axial attn, g=seq FF,
    j=msa axial attn, k=msa FF; reference reversible.py:60-83) followed by a
    cross-attention block (f=seq<-msa cross, g=seq FF, j=msa<-seq cross on
    the UPDATED seq half y2, k=msa FF; reference reversible.py:160-182) —
    note the y2 coupling, whose cotangent path
    (reference reversible.py:213-225) the backward here reproduces;
  * reversibility requires an MSA stream (reference reversible.py:316).

`reverse=False` computes the identical function through plain autodiff
(scan saves carries), mirroring `irreversible_apply`
(reference reversible.py:296-300); it is the oracle for the grad-parity test
(reference tests/test_reversible.py:48-52).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from alphafold2_tpu.models.config import Alphafold2Config
from alphafold2_tpu.models.trunk import (
    cross_apply_grids,
    make_sparse_axial_fn,
    prenorm_axial_apply,
    prenorm_ff_apply,
    trunk_layer_init,
)
from alphafold2_tpu.telemetry.profiling import REVERSIBLE_BWD_SCOPE, scope, scoped


def reversible_trunk_init(key, cfg: Alphafold2Config):
    """Stacked (depth-leading) params for the reversible trunk.

    Stacking per-layer pytrees along a leading depth axis is what lets the
    trunk run as a single scanned body: one compilation of the layer,
    whatever the depth.
    """
    layers = [
        trunk_layer_init(k, cfg, reversible=True)
        for k in jax.random.split(key, cfg.depth)
    ]
    return stack_layers(layers)


def stack_layers(layers):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)


def unstack_layers(stacked):
    """(depth, ...) stacked pytree -> per-layer params list, the inverse
    of `stack_layers` (e.g. to predict with a pipeline-sharded train
    state's trunk through the sequential apply)."""
    depth = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    return [
        jax.tree_util.tree_map(lambda t, i=i: t[i], stacked)
        for i in range(depth)
    ]


# --- the four block functions, parameter-explicit for jax.vjp ---------------


def _f_seq(cfg, params, x2, x_mask, rng, sparse=False):
    # seq axial self-attention (reference reversible f, alphafold2.py:393),
    # block-sparse on layers flagged sparse (reference allows
    # sparse_self_attn with reversible=True, alphafold2.py:349,407-411)
    fn = make_sparse_axial_fn(cfg) if sparse else None
    return prenorm_axial_apply(
        params, cfg.self_attn_config(), x2, mask=x_mask, rng=rng,
        attention_fn=fn,
    )


def _j_msa(cfg, params, m2, msa_mask, rng):
    # msa axial self-attention, optionally tied rows (alphafold2.py:395)
    return prenorm_axial_apply(
        params,
        cfg.self_attn_config(),
        m2,
        mask=msa_mask,
        tie_row=cfg.msa_tie_row_attn,
        rng=rng,
    )


def _ff(cfg, params, t, rng):
    return prenorm_ff_apply(params, cfg, t, rng=rng)


def _cross(cfg, params, q_grid, ctx_grid, q_mask, ctx_mask, rng, direction):
    # cross-attention on grids, flat or column-aligned per
    # cfg.cross_attn_mode, optionally KV-compressed (alphafold2.py:401-403)
    return cross_apply_grids(
        params, cfg, q_grid, ctx_grid, q_mask, ctx_mask, rng, direction
    )


def _op_rngs(rng, layer_idx):
    """Eight per-op dropout keys for one layer, re-derivable in backward."""
    if rng is None:
        return (None,) * 8
    return tuple(jax.random.split(jax.random.fold_in(rng, layer_idx), 8))


# --- one layer forward (used by scan in both primal and fwd rule) -----------


def _layer_forward(cfg, lp, state, x_mask, msa_mask, rngs, sparse=False):
    x1, x2, m1, m2 = state
    (r_fs, r_gs, r_js, r_ks, r_fc, r_gc, r_jc, r_kc) = rngs

    # self-attention block (reference reversible.py:68-83). The seq half
    # (f, g) and msa half (j, k) touch only their own streams — under the
    # branch-parallel schedule they are the layer's two pre-exchange
    # branches, joined (models/trunk.py schedule_join) before the cross
    # block; identical math either way, the reversible inversion below is
    # untouched (the join is the identity)
    y1 = x1 + scoped("seq_attn", _f_seq, cfg, lp["seq_attn"], x2, x_mask, r_fs,
                     sparse)
    y2 = x2 + scoped("seq_ff", _ff, cfg, lp["seq_ff"], y1, r_gs)
    n1 = m1 + scoped("msa_attn", _j_msa, cfg, lp["msa_attn"], m2, msa_mask,
                     r_js)
    n2 = m2 + scoped("msa_ff", _ff, cfg, lp["msa_ff"], n1, r_ks)
    if cfg.trunk_schedule == "branch_parallel":
        from alphafold2_tpu.models.trunk import schedule_join

        (y1, y2), (n1, n2) = schedule_join((y1, y2), (n1, n2))

    # cross-attention block (reference reversible.py:168-182); note the msa
    # cross attends the UPDATED seq half z2
    z1 = y1 + scoped("seq_cross", _cross, cfg, lp["seq_cross"], y2, n2, x_mask,
                     msa_mask, r_fc, "pair_from_msa")
    z2 = y2 + scoped("seq_ff2", _ff, cfg, lp["seq_ff2"], z1, r_gc)
    o1 = n1 + scoped("msa_cross", _cross, cfg, lp["msa_cross"], n2, z2,
                     msa_mask, x_mask, r_jc, "msa_from_pair")
    o2 = n2 + scoped("msa_ff2", _ff, cfg, lp["msa_ff2"], o1, r_kc)

    return (z1, z2, o1, o2)


def _layer_backward(cfg, lp, state, cts, x_mask, msa_mask, rngs, sparse=False):
    """Invert one layer and propagate cotangents (reference
    reversible.py:85-156 and 184-262, re-derived with jax.vjp)."""
    z1, z2, o1, o2 = state
    dz1, dz2, do1, do2 = cts
    (r_fs, r_gs, r_js, r_ks, r_fc, r_gc, r_jc, r_kc) = rngs

    # --- invert cross block (reference reversible.py:184-262) ---
    # k: o2 = n2 + K(o1)
    ko1, k_vjp = jax.vjp(
        lambda p, t: scoped("msa_ff2", _ff, cfg, p, t, r_kc), lp["msa_ff2"], o1)
    n2 = o2 - ko1
    dk, do1_k = k_vjp(do2)
    dn1 = do1 + do1_k
    # j: o1 = n1 + J(n2, z2)  — the y2-coupling (reference :213-225)
    jn2, j_vjp = jax.vjp(
        lambda p, q, c: scoped("msa_cross", _cross, cfg, p, q, c, msa_mask,
                               x_mask, r_jc, "msa_from_pair"),
        lp["msa_cross"],
        n2,
        z2,
    )
    n1 = o1 - jn2
    dj, dn2_j, dz2_j = j_vjp(dn1)
    dn2 = do2 + dn2_j
    dz2_acc = dz2 + dz2_j
    # g: z2 = y2 + G(z1)
    gz1, g_vjp = jax.vjp(
        lambda p, t: scoped("seq_ff2", _ff, cfg, p, t, r_gc), lp["seq_ff2"], z1)
    y2 = z2 - gz1
    dg, dz1_g = g_vjp(dz2_acc)
    dy1 = dz1 + dz1_g
    # f: z1 = y1 + F(y2, n2)
    fy2, f_vjp = jax.vjp(
        lambda p, q, c: scoped("seq_cross", _cross, cfg, p, q, c, x_mask,
                               msa_mask, r_fc, "pair_from_msa"),
        lp["seq_cross"],
        y2,
        n2,
    )
    y1 = z1 - fy2
    df, dy2_f, dn2_f = f_vjp(dy1)
    dy2 = dz2_acc + dy2_f
    dn2 = dn2 + dn2_f

    # --- invert self block (reference reversible.py:85-156) ---
    # seq stream
    gy1, gs_vjp = jax.vjp(
        lambda p, t: scoped("seq_ff", _ff, cfg, p, t, r_gs), lp["seq_ff"], y1)
    x2 = y2 - gy1
    dgs, dy1_g = gs_vjp(dy2)
    dx1 = dy1 + dy1_g
    fx2, fs_vjp = jax.vjp(
        lambda p, t: scoped("seq_attn", _f_seq, cfg, p, t, x_mask, r_fs, sparse),
        lp["seq_attn"], x2
    )
    x1 = y1 - fx2
    dfs, dx2_f = fs_vjp(dx1)
    dx2 = dy2 + dx2_f
    # msa stream
    kn1, ks_vjp = jax.vjp(
        lambda p, t: scoped("msa_ff", _ff, cfg, p, t, r_ks), lp["msa_ff"], n1)
    m2 = n2 - kn1
    dks, dn1_k = ks_vjp(dn2)
    dm1 = dn1 + dn1_k
    jm2, js_vjp = jax.vjp(
        lambda p, t: scoped("msa_attn", _j_msa, cfg, p, t, msa_mask, r_js),
        lp["msa_attn"], m2
    )
    m1 = n1 - jm2
    djs, dm2_j = js_vjp(dm1)
    dm2 = dn2 + dm2_j

    dlp = {
        "seq_attn": dfs,
        "seq_ff": dgs,
        "msa_attn": djs,
        "msa_ff": dks,
        "seq_cross": df,
        "seq_ff2": dg,
        "msa_cross": dj,
        "msa_ff2": dk,
    }
    return (x1, x2, m1, m2), (dx1, dx2, dm1, dm2), dlp


def _num_layers(stacked):
    return jax.tree_util.tree_leaves(stacked)[0].shape[0]


def uniform_flag_runs(flags):
    """[(start, end)] runs of equal per-layer flags — the segmentation
    invariant shared by the reversible trunk, the sequential scan trunk
    (trunk.py), and the segmented multi-execution step
    (training/segmented.py): a scanned layer body is specialized on its
    flag, so segment boundaries must never cross a flag change."""
    runs = []
    start = 0
    for i in range(1, len(flags) + 1):
        if i == len(flags) or flags[i] != flags[start]:
            runs.append((start, i))
            start = i
    return runs


def _scan_forward(meta, stacked, state, x_mask, msa_mask, rng):
    """meta: (cfg, sparse, layer_offset) — static per uniform-flag segment.

    The layer offset keeps `fold_in(rng, layer)` keys GLOBAL layer indices,
    so a segmented trunk (mixed sparse flags) draws the same dropout keys a
    single-segment one would.
    """
    cfg, sparse, offset = meta

    def body(carry, inp):
        lp, li = inp
        return (
            _layer_forward(cfg, lp, carry, x_mask, msa_mask, _op_rngs(rng, li), sparse),
            None,
        )

    L = _num_layers(stacked)
    carry, _ = jax.lax.scan(body, state, (stacked, jnp.arange(offset, offset + L)))
    return carry


# --- the custom-vjp core ----------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _reversible_core(meta, stacked, x1, x2, m1, m2, x_mask, msa_mask, rng):
    return _scan_forward(meta, stacked, (x1, x2, m1, m2), x_mask, msa_mask, rng)


def _reversible_core_fwd(meta, stacked, x1, x2, m1, m2, x_mask, msa_mask, rng):
    out = _scan_forward(meta, stacked, (x1, x2, m1, m2), x_mask, msa_mask, rng)
    # residuals: ONLY the final state (+ params and non-diff aux) — this is
    # the entire point (reference reversible.py:277 saves the same)
    return out, (stacked, out, x_mask, msa_mask, rng)


def _zero_cotangent(x):
    """float0 cotangents for non-differentiable (bool/int) aux arguments."""
    return jax.tree_util.tree_map(
        lambda t: np.zeros(np.shape(t), jax.dtypes.float0), x
    )


def _reversible_core_bwd(meta, residuals, cts):
    cfg, sparse, offset = meta
    stacked, out, x_mask, msa_mask, rng = residuals
    L = _num_layers(stacked)

    def body(carry, inp):
        state, dstate = carry
        lp, li = inp
        state, dstate, dlp = _layer_backward(
            cfg, lp, state, dstate, x_mask, msa_mask, _op_rngs(rng, li), sparse
        )
        return (state, dstate), dlp

    # everything below the marker is the hand-written backward: a
    # `jvp(<op>)` under it is the reconstruction of that op, a
    # `transpose(jvp(<op>))` its backward (benchmarks/scope_reduce.py)
    with scope(REVERSIBLE_BWD_SCOPE):
        (_, (dx1, dx2, dm1, dm2)), dstacked = jax.lax.scan(
            body, (out, cts), (stacked, jnp.arange(offset, offset + L)),
            reverse=True,
        )
    return (
        dstacked,
        dx1,
        dx2,
        dm1,
        dm2,
        _zero_cotangent(x_mask),
        _zero_cotangent(msa_mask),
        _zero_cotangent(rng),
    )


_reversible_core.defvjp(_reversible_core_fwd, _reversible_core_bwd)


# --- public API -------------------------------------------------------------


def reversible_trunk_apply(
    stacked,
    cfg: Alphafold2Config,
    x,
    m,
    *,
    x_mask=None,
    msa_mask=None,
    rng=None,
    reverse: bool = True,
):
    """Run the reversible trunk.

    Args:
      stacked: depth-stacked layer params (reversible_trunk_init), or a list
        of per-layer params (stacked on the fly).
      x: pair representation (b, n, n, d).
      m: MSA stream (b, rows, cols, d) — REQUIRED
        (reference reversible.py:316).
      x_mask: (b, n, n) bool. msa_mask: (b, rows, cols) bool.
      rng: dropout key (None = deterministic).
      reverse: True = O(1)-memory custom-vjp path; False = identical math
        through plain autodiff (the parity oracle,
        reference reversible.py:296-300).

    Returns: (x, m) — the channel-halved streams averaged back to dim d
      (reference reversible.py:327).
    """
    if m is None:
        raise ValueError("the reversible trunk requires an MSA stream "
                         "(reference reversible.py:316)")
    if isinstance(stacked, (list, tuple)):
        stacked = stack_layers(list(stacked))

    # segment the depth by runs of equal sparse flags: each segment scans a
    # uniform layer body through its own reversible core. A uniform config
    # ((False,)*depth or (True,)*depth) is one segment — the original single
    # scan; the reference's interleaved (True, False)*6 becomes 12 chained
    # cores, whose chaining stores one (4-tensor) boundary state per segment
    # — still far below storing every layer.
    flags = cfg.layer_sparse
    segments = uniform_flag_runs(flags)

    state = (x, x, m, m)  # channel-double (reference reversible.py:319)
    for seg_start, seg_end in segments:
        seg = jax.tree_util.tree_map(lambda t: t[seg_start:seg_end], stacked)
        meta = (cfg, flags[seg_start], seg_start)
        if reverse:
            state = _reversible_core(meta, seg, *state, x_mask, msa_mask, rng)
        else:
            state = _scan_forward(meta, seg, state, x_mask, msa_mask, rng)
    z1, z2, o1, o2 = state
    return (z1 + z2) * 0.5, (o1 + o2) * 0.5
