"""The arithmetic of `correct` for a training cell: losses step by step,
the first gradient and the parameters' change by the worst leaf."""
from __future__ import annotations

import statistics

import jax
import jax.numpy as jnp

from common import key_name


def find_mu(opt_state):
    """Adam's first moment inside the optimizer's state."""
    if hasattr(opt_state, "mu"):
        return opt_state.mu
    if isinstance(opt_state, (tuple, list)):
        for part in opt_state:
            found = find_mu(part)
            if found is not None:
                return found
    return None


def leaf_paths(tree):
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(key_name(k) for k in path) for path, _ in leaves]


@jax.jit
def _norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(t.astype(jnp.float32))))
                      for t in jax.tree_util.tree_leaves(tree)])


@jax.jit
def _delta_norms(a, b):
    return _norms(jax.tree_util.tree_map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))


def norms(tree):
    """Host list of each leaf's L2 norm, in `leaf_paths` order."""
    return [float(v) for v in jax.device_get(_norms(tree))]


def delta_norms(a, b):
    return [float(v) for v in jax.device_get(_delta_norms(a, b))]


def median_live(ref):
    live = [v for v in ref if v > 0.0]
    return statistics.median(live) if live else 0.0


def leaf_gaps(prog, ref):
    """Per leaf |‖prog‖ - ‖ref‖| / max(‖ref‖, median leaf's ‖ref‖): the
    gap of norms, not the norm of the difference. The median is over the
    leaves the configuration uses (reference norm above 0)."""
    med = median_live(ref)
    out = []
    for p, r in zip(prog, ref):
        denom = max(r, med)
        out.append(abs(p - r) / denom if denom > 0
                   else (0.0 if p == 0 else float("inf")))
    return out


def worst_leaf_gap(prog, ref, keep=None):
    """(largest of `leaf_gaps` over the leaves kept, index of that leaf)."""
    gaps = leaf_gaps(prog, ref)
    held = [i for i in range(len(gaps)) if keep is None or keep[i]]
    where = max(held, key=lambda i: gaps[i])
    return gaps[where], where


def larger_half(ref):
    """Leaves whose reference norm is the median used leaf's or more."""
    med = median_live(ref)
    return [r >= med and r > 0 for r in ref]


def moved_leaves(ref_grad):
    """Leaves whose reference gradient is a thousandth of the median
    leaf's or more: the others move under Adam by round-off alone."""
    med = median_live(ref_grad)
    return [g >= 1e-3 * med and g > 0 for g in ref_grad]


def rel(a, b):
    return abs(a - b) / abs(b) if b else float("inf")
