"""Communication–compute overlap layer: the shared knobs and gradient
bucketing that turn the multi-chip paths from parity-correct into
latency-hiding.

FastFold (arxiv 2203.00854) and ScaleFold (arxiv 2404.11068) both
attribute their largest AlphaFold2 training wins to exactly two moves:
overlapping collectives with compute and shrinking what sits on the
critical path. This module holds the framework-wide pieces of that story:

  * `overlap_enabled` — ONE resolution point for the overlap on/off knob.
    Every overlapped path (`ring_attention`'s double-buffered schedule,
    the DP-overlap train step) defaults to the environment
    (`AF2_COMM_OVERLAP`, default on) so A/B legs — the MULTICHIP dryrun's
    overlap pair — flip one env var in a subprocess instead of threading
    a flag through every layer.

  * gradient bucketing (`plan_buckets` / `flatten_buckets` /
    `unflatten_buckets`) — the param pytree has hundreds of small leaves
    (norm scales, biases); one psum per leaf would put hundreds of
    latency-bound collectives on the wire per microbatch. Buckets
    coalesce leaves (in pytree order, split on dtype boundaries and a
    size cap) into a few large 1-D arrays, so the overlapped DP step
    (`parallel/train.py make_dp_overlap_train_step`) issues a handful of
    bandwidth-bound all-reduces instead.

The overlapped *schedules* themselves live next to their synchronous
twins: ring attention in `parallel/sequence.py`, the DP-accumulating
step in `parallel/train.py` + `training/harness.py`. The verification
that the overlap structurally exists (collectives not fencing the dots)
is `analysis/overlap_lint.py`.
"""

from __future__ import annotations

import os
from typing import Any, List, Sequence, Tuple

import jax
import jax.numpy as jnp

OVERLAP_ENV = "AF2_COMM_OVERLAP"


def overlap_enabled(override=None) -> bool:
    """Resolve the overlap knob: an explicit True/False wins; None reads
    `AF2_COMM_OVERLAP` (default ON — "0"/"false"/"off" disable; parsed
    in ops/knobs.py, the one home for every AF2_* knob).

    Read at TRACE time: a jitted program bakes the schedule in, so A/B
    harnesses must set the env before tracing (the dryrun and sweep legs
    run each arm in its own subprocess, which guarantees it).
    """
    if override is not None:
        return bool(override)
    from alphafold2_tpu.ops.knobs import comm_overlap_enabled

    return comm_overlap_enabled()


# --- gradient bucketing -----------------------------------------------------

# Default bucket cap: 4M elements = 16 MiB in f32. Large enough that a
# handful of buckets covers the whole model (the psum count stays small),
# small enough that the FIRST bucket's psum can start while later
# microbatch compute still runs.
DEFAULT_BUCKET_ELEMS = 1 << 22


def plan_buckets(tree, bucket_elems: int = DEFAULT_BUCKET_ELEMS):
    """Greedy bucket plan over `tree`'s leaves (abstract or concrete).

    Walks leaves in pytree order, packing consecutive leaves into one
    bucket until the element cap; a dtype change always starts a new
    bucket (a bucket is ONE concatenated 1-D array, so it must be
    dtype-homogeneous). A single leaf larger than the cap gets its own
    bucket. Returns (treedef, buckets) where buckets is a tuple of
    tuples of leaf indices covering every leaf exactly once.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    buckets: List[Tuple[int, ...]] = []
    cur: List[int] = []
    cur_dtype = None
    cur_n = 0
    for i, leaf in enumerate(leaves):
        if cur and (leaf.dtype != cur_dtype or cur_n + leaf.size > bucket_elems):
            buckets.append(tuple(cur))
            cur, cur_n = [], 0
        cur.append(i)
        cur_dtype = leaf.dtype
        cur_n += leaf.size
    if cur:
        buckets.append(tuple(cur))
    return treedef, tuple(buckets)


def flatten_buckets(tree, buckets: Sequence[Tuple[int, ...]]) -> List[Any]:
    """Concatenate `tree`'s leaves into one 1-D array per bucket (the
    wire layout the coalesced psums ride)."""
    leaves = jax.tree_util.tree_flatten(tree)[0]
    return [
        jnp.concatenate([leaves[i].ravel() for i in ix])
        if len(ix) > 1
        else leaves[ix[0]].ravel()
        for ix in buckets
    ]


def unflatten_buckets(flats, shapes_tree, treedef, buckets):
    """Inverse of `flatten_buckets`: split each bucket back into its
    leaves, using `shapes_tree` (a matching pytree of abstract/concrete
    leaves) for shapes and dtypes."""
    leaves = jax.tree_util.tree_flatten(shapes_tree)[0]
    out = [None] * len(leaves)
    for flat, ix in zip(flats, buckets):
        off = 0
        for i in ix:
            size = leaves[i].size
            out[i] = flat[off:off + size].reshape(leaves[i].shape).astype(
                leaves[i].dtype
            )
            off += size
    return jax.tree_util.tree_unflatten(treedef, out)
