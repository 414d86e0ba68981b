"""Plain reference of the end-to-end structure loss that follows the
distogram: softmax -> distances and weights -> classical start -> 25
Guttman iterations with the early stop -> mirror fix by the sign of phi ->
lift to 14 atom slots (carbonyl oxygen by NeRF) -> E(n)-equivariant
refiner -> weighted Kabsch -> RMSD + 0.1 x dispersion of the weights.
float32 and `Precision.HIGHEST` throughout; nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import geometry
from .af2 import blocked, layer_norm, linear, mm

# heavy atoms of each residue, in the alphabet ACDEFGHIKLMNPQRSTVWY, then
# the padding token (none)
HEAVY_ATOMS = np.array([5, 6, 8, 9, 11, 4, 10, 8, 9, 8, 8, 8, 7, 9, 11, 6, 7, 7,
                        14, 12, 0], np.int32)
SLOTS = 14
BOND_C_O, ANGLE_CA_C_O = 1.229, 2.0944


def dihedral(c1, c2, c3, c4):
    u1, u2, u3 = c2 - c1, c3 - c2, c4 - c3
    y = jnp.sum(jnp.linalg.norm(u2, axis=-1, keepdims=True) * u1
                * jnp.cross(u2, u3), axis=-1)
    x = jnp.sum(jnp.cross(u1, u2) * jnp.cross(u2, u3), axis=-1)
    return jnp.arctan2(y, x)


def mds_with_stop(dist, weights, iters, tol=1e-5):
    """Guttman iterations from the classical start (no gradient through
    the start); once the normalised stress improves by `tol` or less the
    coordinates stop moving."""
    n = dist.shape[-1]
    coords = jax.lax.stop_gradient(geometry.classical_start(dist))
    eye = jnp.eye(n, dtype=dist.dtype)
    best = jnp.full((dist.shape[0],), jnp.inf, dist.dtype)
    done = jnp.array(False)
    for _ in range(iters):
        d = geometry.pairwise(coords)
        raw = 0.5 * jnp.sum(weights * jnp.square(d - dist), axis=(-1, -2))
        ratio = weights * (dist / jnp.where(d == 0.0, 1e-7, d))
        b = -ratio + eye[None] * jnp.sum(ratio, axis=-1, keepdims=True)
        new = jnp.matmul(b, coords, precision=jax.lax.Precision.HIGHEST) / n
        now = raw / jnp.linalg.norm(new, axis=(-1, -2))
        done = done | (jnp.mean(best - now) <= tol)
        coords = jnp.where(done, coords, new)
        best = jnp.where(done, best, now)
    return coords  # (b, n, 3)


def fix_mirror(coords):
    """Flip z where fewer than half of the backbone's phi angles are
    negative. coords (b, 3L, 3) ordered N, CA, C per residue."""
    c = jax.lax.stop_gradient(coords)
    n_at, ca, c_at = c[:, 0::3], c[:, 1::3], c[:, 2::3]
    phis = dihedral(c_at[:, :-1], n_at[:, 1:], ca[:, 1:], c_at[:, 1:])
    share = jnp.mean((phis < 0.0).astype(jnp.float32), axis=-1)
    sign = jnp.where(share < 0.5, -1.0, 1.0)[:, None]
    return coords.at[..., 2].multiply(sign)


def nerf(a, b, c, length, theta, chi):
    ba, cb = b - a, c - b
    plane = jnp.cross(ba, cb)
    rot = jnp.stack([cb, jnp.cross(plane, cb), plane], axis=-1)
    rot = rot / jnp.linalg.norm(rot, axis=-2, keepdims=True)
    local = jnp.stack([-jnp.cos(theta), jnp.sin(theta) * jnp.cos(chi),
                       jnp.sin(theta) * jnp.sin(chi)], axis=-1)
    return c + length * jnp.einsum("...ij,...j->...i", rot, local,
                                   precision=jax.lax.Precision.HIGHEST)


def lift(backbone):
    """(b, 3L, 3) -> (b, L, 14, 3): N, CA, C, the carbonyl O opposite psi,
    every other slot parked at C."""
    b, flat, _ = backbone.shape
    bb = backbone.reshape(b, flat // 3, 3, 3)
    park = jnp.broadcast_to(bb[:, :, 2][:, :, None, :], (b, flat // 3, SLOTS - 3, 3))
    psi = dihedral(bb[:, :-1, 0], bb[:, :-1, 1], bb[:, :-1, 2], bb[:, 1:, 0])
    psi = jnp.concatenate([psi, jnp.full((b, 1), np.pi * 5 / 4, backbone.dtype)], axis=1)
    oxygen = nerf(bb[:, :, 0], bb[:, :, 1], bb[:, :, 2], BOND_C_O,
                  jnp.full_like(psi, ANGLE_CA_C_O), psi - np.pi)
    return jnp.concatenate([bb, park], axis=2).at[:, :, 3].set(oxygen)


def refine(p, tokens, coords, mask, *, block=0, q=None):
    """E(n)-equivariant message passing over all atom pairs:
    m_ij = W2 silu(W1 [h_i, h_j, |x_i - x_j|^2]); a_ij = sigmoid(w . m_ij);
    x_i += mean_j a_ij phi(m_ij) (x_i - x_j) / (|x_i - x_j| + 1);
    h_i = LN(h_i + MLP([h_i, mean_j a_ij m_ij]))."""
    a = tokens.shape[1]
    pair = (mask[:, :, None] & mask[:, None, :]) & ~jnp.eye(a, dtype=bool)[None]
    denom = jnp.maximum(jnp.sum(pair, axis=-1, keepdims=True), 1).astype(jnp.float32)
    h = p["token_emb"]["table"][tokens]
    for layer in p["layers"]:
        d = h.shape[-1]
        w1, b1 = layer["edge_mlp"]["l1"]["w"], layer["edge_mlp"]["l1"]["b"]
        hq, hk, w_sq = mm(h, w1[:d], q), mm(h, w1[d:2 * d], q), w1[2 * d]

        def messages(hq_b, xq_b, pair_b, hk=hk, coords=coords, layer=layer,
                     w_sq=w_sq, b1=b1):
            # one block of query atoms against every atom; batch folded out
            diff = xq_b[:, None, :] - coords[0][None, :, :]
            sq = jnp.sum(jnp.square(diff), axis=-1, keepdims=True)
            pre = hq_b[:, None, :] + hk[0][None, :, :] + sq * w_sq + b1
            m = linear(layer["edge_mlp"]["l2"], jax.nn.silu(pre), q)
            gate = jax.nn.sigmoid(linear(layer["att"], m, q))
            gate = jnp.where(pair_b[..., None], gate, 0.0)
            coef = linear(layer["coord_mlp"]["l2"],
                          jax.nn.silu(linear(layer["coord_mlp"]["l1"], m, q)), q)
            direction = (jnp.where(pair_b[..., None], diff, 0.0)
                         / (jnp.sqrt(jnp.maximum(sq, 1e-12)) + 1.0))
            return jnp.concatenate(
                [jnp.sum(gate * coef * direction, axis=1),
                 jnp.sum(gate * m, axis=1)], axis=-1)

        if coords.shape[0] != 1:
            raise ValueError("the reference refines one structure at a time")
        both = blocked(messages, (hq[0], coords[0], pair[0]), block)[None]
        delta, agg = both[..., :3] / denom, both[..., 3:] / denom
        coords = coords + jnp.where(mask[..., None], delta, 0.0)
        upd = linear(layer["node_mlp"]["l2"], jax.nn.silu(linear(
            layer["node_mlp"]["l1"], jnp.concatenate([h, agg], axis=-1), q)), q)
        h = layer_norm(layer["norm"], h + upd)
    return coords


def kabsch(x, y, w):
    """Align x onto y, both (b, 3, A), with point weights w (b, A); the
    rotation carries no gradient."""
    wn = w[:, None, :]
    total = jnp.maximum(jnp.sum(wn, axis=-1, keepdims=True), 1e-8)
    xc = x - jnp.sum(x * wn, axis=-1, keepdims=True) / total
    yc = y - jnp.sum(y * wn, axis=-1, keepdims=True) / total
    cov = jnp.einsum("bdn,ben->bde", xc * wn, yc, precision=jax.lax.Precision.HIGHEST)
    u, _, vt = jnp.linalg.svd(jax.lax.stop_gradient(cov))
    flip = (jnp.linalg.det(u) * jnp.linalg.det(vt) < 0.0)[:, None]
    u = u.at[:, :, -1].set(jnp.where(flip, -u[:, :, -1], u[:, :, -1]))
    rot = jnp.einsum("bij,bjk->bik", u, vt, precision=jax.lax.Precision.HIGHEST)
    return jnp.einsum("bji,bjn->bin", rot, xc,
                      precision=jax.lax.Precision.HIGHEST), yc


def structure_loss(logits, refiner, batch, hp, q=None):
    """The loss from the distogram logits (b, 3L, 3L, B) on."""
    seq, truth = batch["seq"], batch["coords"]
    b, length = seq.shape
    probs = jax.nn.softmax(logits, axis=-1)
    dist, weights = geometry.center_distogram(probs)
    backbone = fix_mirror(mds_with_stop(dist, weights, hp["mds_iters"]))
    cloud = lift(backbone)
    present = jnp.arange(SLOTS)[None, None, :] < jnp.asarray(HEAVY_ATOMS)[seq][..., None]
    atoms = length * SLOTS
    tokens = jnp.broadcast_to(jnp.arange(SLOTS)[None, None, :], present.shape)
    refined = refine(refiner, tokens.reshape(b, atoms), cloud.reshape(b, atoms, 3),
                     present.reshape(b, atoms), block=hp["atom_block"], q=q)
    w = present.reshape(b, atoms).astype(jnp.float32)
    pred = jnp.transpose(refined, (0, 2, 1))
    true = jnp.transpose(truth.reshape(b, atoms, 3), (0, 2, 1))
    aligned, centred = kabsch(pred, true, w)
    sq = jnp.sum(jnp.square(aligned - centred), axis=-2)
    rmsd = jnp.sqrt(jnp.sum(sq * w, axis=-1) / jnp.maximum(jnp.sum(w, axis=-1), 1.0))
    valid = (weights > 0).astype(jnp.float32)
    spread = jnp.sum(jnp.abs(1.0 / (weights + 1e-3) - 1.0) * valid) / jnp.maximum(
        jnp.sum(valid), 1.0)
    return jnp.mean(rmsd) + 0.1 * spread
