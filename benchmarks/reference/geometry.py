"""Plain reference of the geometry under the structure loss: distogram
-> distances and weights, the classical (Torgerson) start, pairwise
distances. float32, `Precision.HIGHEST`, nothing imported from the
program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def bin_centers(num_buckets=37):
    """Centres of the distance buckets whose upper thresholds are
    linspace(2, 20, n): half a bin below the threshold, the first at
    1.5 A, the catch-all last at 1.33 x the final threshold."""
    bins = jnp.linspace(2.0, 20.0, num_buckets)
    centers = bins - 0.5 * (bins[2] - bins[1])
    centers = centers.at[0].set(1.5).at[-1].set(1.33 * bins[-1])
    return bins, centers


def center_distogram(probs):
    """Mean distance per pair, and a weight 1 / (1 + std) that is zero
    where the mean falls into the catch-all bucket. Zero diagonal."""
    bins, centers = bin_centers(probs.shape[-1])
    central = jnp.einsum("...b,b->...", probs, centers, precision=HI)
    keep = (central <= bins[-2]).astype(probs.dtype)
    n = probs.shape[-2]
    central = jnp.where(jnp.eye(n, dtype=bool), 0.0, central)
    var = jnp.einsum("...b,...b->...", probs,
                     jnp.square(centers - central[..., None]), precision=HI)
    return central, keep / (1.0 + jnp.sqrt(var))


def classical_start(dist):
    """Top-3 eigenpairs of the double-centred squared distances."""
    d2 = jnp.square(dist)
    b = -0.5 * (d2 - jnp.mean(d2, axis=-1, keepdims=True)
                - jnp.mean(d2, axis=-2, keepdims=True)
                + jnp.mean(d2, axis=(-1, -2), keepdims=True))
    evals, evecs = jnp.linalg.eigh(b)
    return evecs[..., -3:] * jnp.sqrt(jnp.clip(evals[..., -3:], 0.0))[..., None, :]


def pairwise(coords):
    d2 = jnp.sum(jnp.square(coords[:, :, None, :] - coords[:, None, :, :]), axis=-1)
    return jnp.sqrt(d2 + 1e-12)
