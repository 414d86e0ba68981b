"""The training cells' feed: one example of the stated shape as a function
of (seed, index), so that the same seed gives the same examples."""
from __future__ import annotations

import numpy as np


def train_batch(shape: dict, seed: int, index: int) -> dict:
    """One full-atom training example of the stated shape, a function of
    (seed, index): N, CA, C of every residue on a noisy right-handed helix,
    every other atom slot parked at C; the residue and alignment tokens
    uniform; full masks. The sequence is NOT elongated (the loss does
    that)."""
    rng = np.random.default_rng([seed, 3, index])
    crop, rows = shape["crop"], shape["msa_rows"]
    atoms = crop * shape["atoms_per_residue"]
    seq = rng.integers(0, 21, size=(1, crop)).astype(np.int32)
    msa = rng.integers(0, 21, size=(1, rows, crop)).astype(np.int32)
    t = 0.6 * np.arange(atoms)[None, :, None]
    helix = np.concatenate([2 * np.cos(t), 2 * np.sin(t), -0.16 * t], axis=-1)
    coords = (helix + 0.05 * rng.standard_normal((1, atoms, 3))).astype(np.float32)
    bb = coords.reshape(1, crop, 3, 3)
    park = np.broadcast_to(bb[:, :, 2][:, :, None, :], (1, crop, 11, 3))
    return {"seq": seq, "mask": np.ones((1, crop), bool), "msa": msa,
            "msa_mask": np.ones((1, rows, crop), bool),
            "coords": np.concatenate([bb, park], axis=2)}
