"""The dual-track trunk: pair-representation and MSA streams.

Re-design of the reference `SequentialSequence`
(reference alphafold2_pytorch/alphafold2.py:290-326). The reference keeps the
pair representation flattened to (b, n*n, d) and reshapes per axial pass; here
both streams stay in their natural grid layouts — pair (b, i, j, d), MSA
(b, rows, cols, d) — and only the cross-attention flattens, which keeps the
sharding story simple (the grid axes are the mesh axes, see parallel/).

Per layer, every op residual (reference alphafold2.py:309-324):
  pair axial self-attn -> msa axial self-attn (optionally tied rows) ->
  pair<-msa cross-attn (optionally KV-compressed) -> msa<-pair cross-attn ->
  pair FF -> msa FF.
The MSA branch is skipped entirely when no MSA stream exists
(reference alphafold2.py:311).

Trunk schedules (cfg.trunk_schedule; docs/ARCHITECTURE.md "Trunk
schedules"): the per-layer dataflow above has exactly one cross-track
dependency — the cross-attention exchange. Everything before it (each
track's self-attention) and after it (each track's feed-forward) touches
only its own stream, so the Parallel-Evoformer observation (arXiv
2211.00235) applies: the pair track and the MSA track are two independent
BRANCHES that join only at the exchange. "serial" emits the reference
op order; "branch_parallel" emits the SAME ops re-grouped as explicit
branches whose results meet at a `schedule_join` marker (an
optimization-barrier the compiler's latency-hiding scheduler — and
analysis/schedule_lint.py — can see). Identical math, allclose fwd +
grads; the join also pins the schedule: nothing from one branch may be
interleaved past the join into the other.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from alphafold2_tpu.models.config import Alphafold2Config
from alphafold2_tpu.ops.attention import (
    attention_apply,
    attention_init,
    axial_attention_apply,
    axial_attention_init,
)
from alphafold2_tpu.ops.core import layer_norm, layer_norm_init
from alphafold2_tpu.ops.feedforward import feed_forward_apply, feed_forward_init
from alphafold2_tpu.ops.sparse import sparse_attention_apply
from alphafold2_tpu.telemetry.profiling import scoped


_REMAT_POLICIES = {
    None: None,
    "dots": "dots_saveable",
    "dots_no_batch": "dots_with_no_batch_dims_saveable",
}


# --- the branch-parallel schedule join ---------------------------------------


@jax.custom_vjp
def _join_barrier(args):
    return jax.lax.optimization_barrier(args)


def _join_barrier_fwd(args):
    return _join_barrier(args), None


def _join_barrier_bwd(_, cts):
    return (cts,)


# identity with an explicit gradient rule: the barrier is a schedule
# marker, not math — cotangents pass straight through (the backward
# program carries no barrier)
_join_barrier.defvjp(_join_barrier_fwd, _join_barrier_bwd)


def schedule_join(*branches):
    """JOIN the branch-parallel schedule's independent branches.

    Emits ONE multi-operand `stablehlo.optimization_barrier` over every
    tensor of every branch. Semantically the identity (gradients pass
    through untouched); structurally it is the schedule contract the
    trunk claims and analysis/schedule_lint.py verifies:

      * nothing downstream of the join can be hoisted into a branch, and
        no branch op can sink past the join — the branches are
        schedulable as whole concurrent units;
      * the lint finds each join in the lowered StableHLO and asserts its
        operands split into >= 2 groups with DISJOINT compute slices
        (no shared dot/reduce/conv) — i.e. the branches really are
        data-independent before the join. A serialized twin (one branch
        coupled behind the other, `serialize_twin` below) must be
        flagged by the same check.

    Each branch is a tensor or tuple of tensors; returns them in the
    same structure."""
    flat, treedef = jax.tree_util.tree_flatten(branches)
    out = _join_barrier(tuple(flat))
    return jax.tree_util.tree_unflatten(treedef, out)


def schedule_fork(t):
    """Mark the START of a new branch region after a cross-track exchange.

    A SINGLE-operand barrier (identity, gradient passes through): the
    schedule lint exempts it from join analysis (joins have >= 2
    operands) but its slice walk stops here, so each join's pre-join
    region covers exactly its own layer's branches — without the fork,
    layer N+1's join would see layer N's (legitimately cross-track)
    exchange in both branch slices and read as serialized. Schedule-wise
    it pins the exchange ahead of the post-exchange branches."""
    (out,) = _join_barrier((t,))
    return out


def _remat_policy(cfg: Alphafold2Config):
    # membership is validated eagerly in Alphafold2Config.__post_init__
    name = _REMAT_POLICIES[cfg.remat_policy]
    return getattr(jax.checkpoint_policies, name) if name else None


def make_sparse_axial_fn(cfg: Alphafold2Config):
    """Inner-attention override running each axial pass block-sparsely.

    Replaces the dense inner attention with the variable-sparsity pattern
    for layers flagged in cfg.layer_sparse — the reference applies sparse
    attention to the pair-rep (seq) axial passes only
    (reference alphafold2.py:393), never to tied-row MSA attention
    (reference alphafold2.py:192).
    """
    attn_cfg = cfg.self_attn_config()
    scfg = cfg.sparse_config()

    def fn(params, x, *, axis, mask, tie_dim, rng, **ctx):
        del axis
        if ctx:
            raise ValueError("sparse attention is self-attention only")
        if tie_dim is not None:
            raise ValueError(
                "sparse attention is incompatible with tied-row attention "
                "(reference alphafold2.py:192)"
            )
        return sparse_attention_apply(
            params, attn_cfg, scfg, x, mask=mask, rng=rng,
            use_kernel=cfg.sparse_use_kernel,
        )

    return fn


# --- pre-norm wrapped blocks ------------------------------------------------


def prenorm_axial_init(key, cfg: Alphafold2Config, attn_cfg):
    return {"norm": layer_norm_init(cfg.dim), "attn": axial_attention_init(key, attn_cfg)}


def prenorm_cross_init(key, cfg: Alphafold2Config, attn_cfg):
    return {
        "norm": layer_norm_init(cfg.dim),
        "norm_context": layer_norm_init(cfg.dim),
        "attn": attention_init(key, attn_cfg),
    }


def prenorm_ff_init(key, cfg: Alphafold2Config):
    return {"norm": layer_norm_init(cfg.dim), "ff": feed_forward_init(key, cfg.dim)}


def prenorm_axial_apply(params, attn_cfg, x, **kwargs):
    return axial_attention_apply(params["attn"], attn_cfg, layer_norm(params["norm"], x), **kwargs)


def prenorm_cross_apply(params, attn_cfg, x, context, **kwargs):
    return attention_apply(
        params["attn"],
        attn_cfg,
        layer_norm(params["norm"], x),
        context=layer_norm(params["norm_context"], context),
        **kwargs,
    )


def prenorm_ff_apply(params, cfg: Alphafold2Config, x, rng=None):
    return feed_forward_apply(
        params["ff"],
        layer_norm(params["norm"], x),
        dropout_rate=cfg.ff_dropout,
        rng=rng,
        dtype=cfg.dtype,
        chunk=cfg.ff_chunk_size,
    )


# --- cross-attention over grids: flat vs column-aligned ---------------------


def _fold_by_msa_column(x, m, x_mask, msa_mask):
    """Group pair-grid columns by the MSA column they map to.

    Pair grid (b, n, n, d) with n = f*c (f = residue elongation factor, e.g.
    3 backbone atoms per residue, reference train_end2end.py:134-146); MSA
    (b, r, c, d). Returns per-column folds:
      xg (b*c, n*f, d) — the pair tokens whose grid column maps to column c;
      mg (b*c, r, d)   — that column's MSA residues;
    plus the matching folded masks (or None).
    """
    b, n, n2, d = x.shape
    r, c = m.shape[1], m.shape[2]
    if n != n2 or n % c != 0:
        raise ValueError(
            f"aligned cross-attention needs a square pair grid whose side is "
            f"a multiple of the MSA column count; got pair ({n}, {n2}), "
            f"msa cols {c}"
        )
    f = n // c
    xg = x.reshape(b, n, c, f, d).transpose(0, 2, 1, 3, 4).reshape(b * c, n * f, d)
    mg = jnp.swapaxes(m, 1, 2).reshape(b * c, r, d)
    xg_mask = (
        x_mask.reshape(b, n, c, f).transpose(0, 2, 1, 3).reshape(b * c, n * f)
        if x_mask is not None
        else None
    )
    mg_mask = (
        jnp.swapaxes(msa_mask, 1, 2).reshape(b * c, r)
        if msa_mask is not None
        else None
    )
    return xg, mg, xg_mask, mg_mask, f


def _unfold_pair(xg, b, n, f, d):
    c = xg.shape[0] // b
    return xg.reshape(b, c, n, f, d).transpose(0, 2, 1, 3, 4).reshape(b, n, n, d)


def _unfold_msa(mg, b, r, d):
    c = mg.shape[0] // b
    return jnp.swapaxes(mg.reshape(b, c, r, d), 1, 2)


def cross_apply_grids(
    params, cfg: Alphafold2Config, q_grid, ctx_grid, q_mask, ctx_mask, rng, direction
):
    """Pre-norm cross-attention between the pair and MSA streams, on grids.

    direction: "pair_from_msa" (q_grid = pair (b,n,n,d), ctx = MSA
    (b,r,c,d)) or "msa_from_pair" (the mirror). Dispatches on
    cfg.cross_attn_mode:

      * "flat" — both streams fully flattened, every query attends every
        context token (reference alphafold2.py:316-317). O(n^2 * r*c)
        logits; blockwise-streamed at scale but FLOP-bound beyond small
        crops.
      * "aligned" — each pair token attends only the MSA column its grid
        column maps to; each MSA token attends only its column's pair-grid
        block. The column fold becomes the attention batch: O(n^2 * r)
        total. KV compression still applies along the folded key axis.

    Returns the attention output in the query grid's layout (pre-residual).
    """
    cross_cfg = cfg.cross_attn_config()
    if cfg.cross_attn_mode == "flat":
        qb = q_grid.shape[0]
        d = q_grid.shape[-1]
        qf = q_grid.reshape(qb, -1, d)
        cf = ctx_grid.reshape(qb, -1, d)
        qm = q_mask.reshape(qb, -1) if q_mask is not None else None
        cm = ctx_mask.reshape(qb, -1) if ctx_mask is not None else None
        out = prenorm_cross_apply(
            params, cross_cfg, qf, cf, mask=qm, context_mask=cm, rng=rng
        )
        return out.reshape(q_grid.shape)

    # aligned
    b = q_grid.shape[0]
    d = q_grid.shape[-1]
    if direction == "pair_from_msa":
        x, m = q_grid, ctx_grid
        xg, mg, xg_mask, mg_mask, f = _fold_by_msa_column(x, m, q_mask, ctx_mask)
        out = prenorm_cross_apply(
            params, cross_cfg, xg, mg, mask=xg_mask, context_mask=mg_mask, rng=rng
        )
        return _unfold_pair(out, b, x.shape[1], f, d)
    elif direction == "msa_from_pair":
        m, x = q_grid, ctx_grid
        xg, mg, xg_mask, mg_mask, f = _fold_by_msa_column(x, m, ctx_mask, q_mask)
        out = prenorm_cross_apply(
            params, cross_cfg, mg, xg, mask=mg_mask, context_mask=xg_mask, rng=rng
        )
        return _unfold_msa(out, b, m.shape[1], d)
    raise ValueError(f"unknown cross direction {direction!r}")


# --- trunk layer ------------------------------------------------------------


def trunk_layer_init(key, cfg: Alphafold2Config, *, reversible: bool = False):
    """One trunk layer's params.

    Sequential layers carry 6 blocks; reversible layers carry 8 — the
    reference drops the 4th feed-forward of each half-layer when sequential
    (reference alphafold2.py:407-408).
    """
    keys = jax.random.split(key, 8)
    self_cfg = cfg.self_attn_config()
    cross_cfg = cfg.cross_attn_config()
    params = {
        "seq_attn": prenorm_axial_init(keys[0], cfg, self_cfg),
        "msa_attn": prenorm_axial_init(keys[1], cfg, self_cfg),
        "seq_cross": prenorm_cross_init(keys[2], cfg, cross_cfg),
        "msa_cross": prenorm_cross_init(keys[3], cfg, cross_cfg),
        "seq_ff": prenorm_ff_init(keys[4], cfg),
        "msa_ff": prenorm_ff_init(keys[5], cfg),
    }
    if reversible:
        params["seq_ff2"] = prenorm_ff_init(keys[6], cfg)
        params["msa_ff2"] = prenorm_ff_init(keys[7], cfg)
    return params


def trunk_layer_apply(
    layer,
    cfg: Alphafold2Config,
    x,
    m,
    *,
    x_mask=None,
    msa_mask=None,
    rngs=(None,) * 6,
    sparse_fn=None,
):
    """ONE sequential trunk layer — the single source of the layer order
    (reference alphafold2.py:309-324), shared by the sequential trunk here
    and the pipeline-parallel trunk (parallel/pipeline.py).

    rngs: six per-op dropout keys (None = deterministic). sparse_fn: inner
    block-sparse attention override for the pair self-attention pass, or
    None for dense.

    cfg.trunk_schedule selects the intra-layer schedule: "serial" runs
    the reference order below; "branch_parallel" runs the SAME ops with
    the two tracks' self-attentions grouped as independent branches that
    join (schedule_join) at the cross-attention exchange — identical
    dataflow, explicit branch structure. Layers without an MSA stream
    have a single track and always run serially.
    """
    if cfg.trunk_schedule == "branch_parallel" and m is not None:
        return branch_parallel_layer_apply(
            layer, cfg, x, m,
            x_mask=x_mask, msa_mask=msa_mask, rngs=rngs, sparse_fn=sparse_fn,
        )
    self_cfg = cfg.self_attn_config()
    # pair axial self-attention (reference alphafold2.py:309), with the
    # block-sparse inner attention when sparse_fn is given — applied PER
    # LAYER, fixing the reference bug that ignores the per-layer tuple
    # (reference alphafold2.py:392)
    x = scoped(
        "seq_attn",
        prenorm_axial_apply,
        layer["seq_attn"],
        self_cfg,
        x,
        mask=x_mask,
        rng=rngs[0],
        attention_fn=sparse_fn,
    ) + x

    if m is not None:
        # msa axial self-attention, optionally tied rows
        # (reference alphafold2.py:312)
        m = scoped(
            "msa_attn",
            prenorm_axial_apply,
            layer["msa_attn"],
            self_cfg,
            m,
            mask=msa_mask,
            tie_row=cfg.msa_tie_row_attn,
            rng=rngs[1],
        ) + m

        # cross-attention both ways, flat or column-aligned
        # (reference alphafold2.py:316-317; cfg.cross_attn_mode)
        x = scoped(
            "seq_cross", cross_apply_grids,
            layer["seq_cross"], cfg, x, m, x_mask, msa_mask,
            rngs[2], "pair_from_msa",
        ) + x
        m = scoped(
            "msa_cross", cross_apply_grids,
            layer["msa_cross"], cfg, m, x, msa_mask, x_mask,
            rngs[3], "msa_from_pair",
        ) + m

    # feed-forwards (reference alphafold2.py:321-324)
    x = scoped("seq_ff", prenorm_ff_apply, layer["seq_ff"], cfg, x,
               rng=rngs[4]) + x
    if m is not None:
        m = scoped("msa_ff", prenorm_ff_apply, layer["msa_ff"], cfg, m,
                   rng=rngs[5]) + m
    return x, m


def branch_parallel_layer_apply(
    layer,
    cfg: Alphafold2Config,
    x,
    m,
    *,
    x_mask=None,
    msa_mask=None,
    rngs=(None,) * 6,
    sparse_fn=None,
    serialize_twin: bool = False,
):
    """ONE trunk layer under the BRANCH-PARALLEL schedule.

    The same six residual ops as the serial `trunk_layer_apply` — same
    params, same rng slots, allclose fwd + grads — re-grouped into the
    Parallel-Evoformer branch structure (arXiv 2211.00235):

        pair branch:  x += pair_self_attn(x)     \\  independent,
        msa  branch:  m += msa_self_attn(m)      /   schedulable together
        ---------------- schedule_join ----------------
        exchange:     x += cross(x, m); m += cross(m, x)
        pair branch:  x += pair_ff(x)            \\  independent again
        msa  branch:  m += msa_ff(m)             /   (joins at the NEXT
                                                      layer's exchange)

    Between consecutive exchanges each track's ops (this layer's FF, the
    next layer's self-attention) form one contiguous data-independent
    branch, so one join per layer — placed immediately before the
    exchange — pins the whole schedule.

    serialize_twin: the schedule-lint fixture (analysis/schedule_lint.py
    self-check) — couples the MSA branch's input behind the pair branch's
    output through an identity barrier, producing exactly the lowered
    structure a re-serialized schedule would have. Numerics unchanged;
    never set outside the lint/tests.
    """
    self_cfg = cfg.self_attn_config()

    x1 = scoped(
        "seq_attn", prenorm_axial_apply,
        layer["seq_attn"], self_cfg, x,
        mask=x_mask, rng=rngs[0], attention_fn=sparse_fn,
    ) + x
    if serialize_twin:
        # deliberately thread the MSA branch behind the pair branch via an
        # exact-identity arithmetic coupling (+ 0 * sum(pair branch)): the
        # join below then has overlapping operand slices — the pair
        # branch's dots reach the MSA operand — which the schedule lint
        # must flag (detector self-check). A barrier could not serve here:
        # the lint's slice walk deliberately stops at barriers (each join
        # scopes its own pre-join region), so the coupling must flow
        # through ordinary value ops.
        m = m + (0.0 * jnp.sum(x1)).astype(m.dtype)
    m1 = scoped(
        "msa_attn", prenorm_axial_apply,
        layer["msa_attn"], self_cfg, m,
        mask=msa_mask, tie_row=cfg.msa_tie_row_attn, rng=rngs[1],
    ) + m

    x1, m1 = schedule_join(x1, m1)

    # the exchange (reference alphafold2.py:316-317): the ONLY cross-track
    # dataflow — msa<-pair reads the UPDATED pair stream, like serial
    x2 = scoped(
        "seq_cross", cross_apply_grids,
        layer["seq_cross"], cfg, x1, m1, x_mask, msa_mask,
        rngs[2], "pair_from_msa",
    ) + x1
    m2 = scoped(
        "msa_cross", cross_apply_grids,
        layer["msa_cross"], cfg, m1, x2, msa_mask, x_mask,
        rngs[3], "msa_from_pair",
    ) + m1

    # post-exchange branches (they run up to the next layer's join); the
    # forks close the exchange region so the NEXT join's branch slices
    # start here instead of reaching back through the shared exchange
    x2 = schedule_fork(x2)
    m2 = schedule_fork(m2)
    x3 = scoped("seq_ff", prenorm_ff_apply, layer["seq_ff"], cfg, x2,
                rng=rngs[4]) + x2
    m3 = scoped("msa_ff", prenorm_ff_apply, layer["msa_ff"], cfg, m2,
                rng=rngs[5]) + m2
    return x3, m3


def sequential_trunk_apply(
    layers,
    cfg: Alphafold2Config,
    x,
    m,
    *,
    x_mask=None,
    msa_mask=None,
    rng=None,
):
    """Run the sequential trunk.

    Args:
      layers: list of trunk_layer_init params.
      x: pair representation (b, n, n, d).
      m: MSA stream (b, rows, cols, d) or None.
      x_mask: (b, n, n) bool.
      msa_mask: (b, rows, cols) bool.
      rng: dropout key (None = deterministic).

    Returns: (x, m) in the same layouts.
    """
    layer_sparse = cfg.layer_sparse
    sparse_fn = make_sparse_axial_fn(cfg) if any(layer_sparse) else None

    def one_layer(sparse_this_layer):
        def body(layer, x, m, rngs):
            return trunk_layer_apply(
                layer, cfg, x, m,
                x_mask=x_mask, msa_mask=msa_mask, rngs=rngs,
                sparse_fn=sparse_fn if sparse_this_layer else None,
            )

        if cfg.remat:
            # recompute this layer's activations in the backward pass
            # instead of storing them: O(1) trunk activation memory in
            # depth, the jax.checkpoint sibling of the reversible trunk
            # (reference reversible.py's motivation, SURVEY.md §2.2).
            # cfg.remat_policy trades memory back for backward FLOPs by
            # saving matmul outputs (models/config.py)
            return jax.checkpoint(body, policy=_remat_policy(cfg))
        return body

    if cfg.scan_layers:
        # scan each uniform-sparse-flag run of layers as ONE compiled body
        # (depth-stacked params), mirroring the reversible trunk's
        # segmentation (models/reversible.py). Per-layer dropout keys are
        # re-derived from the GLOBAL layer index inside the scan, so the
        # unrolled and scanned trunks draw identical masks.
        #
        # The in-trace jnp.stack copies the trunk params once per step
        # (~2 ms of HBM traffic per GB at v5e) — negligible against the
        # tens-of-seconds steps this flag exists for; the win is compile
        # time (one layer body instead of `depth` clones). Keep params as
        # the plain layer list so every trunk variant (SP, pipeline,
        # converter) shares one layout.
        from alphafold2_tpu.models.reversible import stack_layers

        segments = []
        start = 0
        for i in range(1, len(layers) + 1):
            if i == len(layers) or layer_sparse[i] != layer_sparse[start]:
                segments.append((start, i))
                start = i

        for seg_start, seg_end in segments:
            stacked = stack_layers(layers[seg_start:seg_end])
            body = one_layer(layer_sparse[seg_start])

            def scan_body(carry, inp):
                lp, li = inp
                cx, cm = carry
                lrng = jax.random.fold_in(rng, li) if rng is not None else None
                rngs = (
                    jax.random.split(lrng, 6) if lrng is not None else [None] * 6
                )
                return body(lp, cx, cm, rngs), None

            (x, m), _ = jax.lax.scan(
                scan_body, (x, m), (stacked, jnp.arange(seg_start, seg_end))
            )
        return x, m

    for li, layer in enumerate(layers):
        lrng = jax.random.fold_in(rng, li) if rng is not None else None
        rngs = (
            jax.random.split(lrng, 6) if lrng is not None else [None] * 6
        )
        x, m = one_layer(layer_sparse[li])(layer, x, m, rngs)

    return x, m
