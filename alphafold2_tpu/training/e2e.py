"""End-to-end structure training: distogram -> 3D coords -> refiner -> loss.

This implements the pipeline the reference *intended* in `train_end2end.py`
(which does not run as-is — see the defect list in SURVEY.md §3.2): model
forward on the x3-elongated backbone sequence (train_end2end.py:134-149),
distogram centering (:152), MDS with mirror fix (:154-160), sidechain
container lifting (:163), SE(3)-equivariant refinement (:168-169), Kabsch
alignment (:172) and RMSD + distogram-dispersion loss (:175-176).

Everything is one differentiable jitted graph: gradients flow through the
refiner, the sidechain lift, the Guttman MDS iterations, and the distogram
centering back into the trunk — the same coupling the reference loss
depends on.

Deliberate fixes vs the reference script:
  * elongated residues are fed directly as repeated tokens (the reference's
    `pos_tokens=3` kwarg does not exist on its own model, train_end2end.py:80);
  * `1/weights` in the dispersion term is `1/(weights + eps)` — reference
    divides by exact zeros for censored distogram bins (train_end2end.py:176);
  * Kabsch uses static-shape weighted alignment instead of boolean indexing
    (train_end2end.py:172 breaks under jit; see geometry/kabsch.py).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from alphafold2_tpu.constants import NUM_COORDS_PER_RES
from alphafold2_tpu.geometry import (
    center_distogram,
    kabsch,
    mdscaling,
    scn_backbone_mask,
    scn_cloud_mask,
    sidechain_container,
)
from alphafold2_tpu.models import (
    Alphafold2Config,
    RefinerConfig,
    alphafold2_apply,
    alphafold2_init,
    refiner_apply,
    refiner_init,
)
from alphafold2_tpu.telemetry.profiling import scope, scoped


@dataclasses.dataclass(frozen=True)
class E2EConfig:
    """Hashable config for the full structure workload (BASELINE config 5)."""

    model: Alphafold2Config
    refiner: RefinerConfig = RefinerConfig(num_tokens=NUM_COORDS_PER_RES)
    mds_iters: int = 200  # reference train_end2end.py:157
    # truncate MDS backprop to the last K Guttman iterations (None = full
    # unroll). Near convergence this approximates implicit differentiation
    # (geometry/mds.py) and removes iters-K per-iteration (3L, 3L) residuals
    # from the backward — the MDS unroll is a dominant latency/memory cost
    # at the north-star scale (PERF.md)
    mds_bwd_iters: int | None = None
    # lax.scan unroll factor for the MDS iterations (geometry/mds.py):
    # amortizes per-iteration dispatch overhead on TPU (same math; float
    # reassociation noise only)
    mds_unroll: int = 1
    # "random" (reference parity) or "classical": Torgerson eigendecomposition
    # warm start — reaches the random-init stress floor in ~1 iteration on
    # both exact and distogram-censored real inputs (geometry/mds.py), so
    # pairing it with a small mds_iters removes most of the sequential
    # Guttman tail from the step
    mds_init: str = "random"
    fix_mirror: bool = True  # reference fix_mirror=5 -> boolean here; the
    # reference's int is a retry count for an eigen-fallback that its own
    # mds_torch never triggers (utils.py:637-642)
    place_oxygen: bool = True
    dispersion_weight: float = 0.1  # reference train_end2end.py:176
    weights_eps: float = 1e-3


def elongate(seq, factor: int = 3):
    """Repeat each residue token `factor` times: (b, L) -> (b, L*factor)
    (reference train_end2end.py:134-141 — one token per backbone atom)."""
    return jnp.repeat(seq, factor, axis=-1)


def predict_structure(params, ecfg: E2EConfig, seq, mask=None, rng=None, msa=None, msa_mask=None, embedds=None, templates=None, templates_mask=None, model_apply_fn=None):
    """Full forward: sequence -> refined (b, L, 14, 3) atom cloud.

    params: {"model": ..., "refiner": ...}.

    model_apply_fn: override for the trunk forward with the
    alphafold2_apply signature — e.g. the sequence-parallel apply
    (parallel/train.py sp_e2e_loss_fn). Geometry, MDS, and the refiner
    always run replicated (negligible FLOPs/memory share).

    Returns dict with refined cloud, proto cloud, distogram weights, and the
    atom cloud mask.
    """
    apply_fn = model_apply_fn if model_apply_fn is not None else alphafold2_apply
    b, length = seq.shape
    seq3 = elongate(seq)
    mask3 = elongate(mask) if mask is not None else None

    if rng is not None:
        rng_model, rng_mds = jax.random.split(rng)
    else:
        rng_model, rng_mds = None, jax.random.PRNGKey(0)

    # templates are over the ELONGATED (3L, 3L) pair grid — the trunk's
    # pair axes after the x3 backbone-atom expansion (one token per N/CA/C)
    tmpl_kwargs = (
        {"templates": templates, "templates_mask": templates_mask}
        if templates is not None
        else {}
    )
    logits = apply_fn(
        params["model"], ecfg.model, seq3, msa,
        mask=mask3, msa_mask=msa_mask, embedds=embedds, rng=rng_model,
        **tmpl_kwargs,
    )  # (b, 3L, 3L, buckets)
    # geometry runs in float32 regardless of the trunk's compute dtype:
    # the distogram -> MDS pipeline divides by pairwise distances (Guttman
    # B-matrix) and small weights, which overflows/NaNs in bfloat16
    with scope("center_distogram"):
        logits = logits.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        distances, weights = center_distogram(probs)

    # chirality masks over the flat (L*3) backbone atom axis
    n_mask, ca_mask = scn_backbone_mask(seq, l_aa=3)
    coords, _ = scoped(
        "mds",
        mdscaling,
        distances,
        weights=weights,
        iters=ecfg.mds_iters,
        fix_mirror=ecfg.fix_mirror,
        N_mask=n_mask,
        CA_mask=ca_mask,
        key=rng_mds,
        bwd_iters=ecfg.mds_bwd_iters,
        unroll=ecfg.mds_unroll,
        init=ecfg.mds_init,
    )  # (b, 3, 3L)

    with scope("sidechain_lift"):
        backbone = jnp.transpose(coords, (0, 2, 1))  # (b, 3L, 3)
        proto = sidechain_container(backbone, place_oxygen=ecfg.place_oxygen)  # (b, L, 14, 3)

    cloud_mask = scn_cloud_mask(seq)  # (b, L, 14)
    if mask is not None:
        cloud_mask = cloud_mask & mask[..., None]

    num_atoms = length * NUM_COORDS_PER_RES
    atom_tokens = jnp.broadcast_to(
        jnp.arange(NUM_COORDS_PER_RES)[None, None, :], cloud_mask.shape
    ).reshape(b, num_atoms)
    refined, _ = scoped(
        "refiner", refiner_apply,
        params["refiner"], ecfg.refiner,
        atom_tokens, proto.reshape(b, num_atoms, 3),
        mask=cloud_mask.reshape(b, num_atoms),
    )
    return {
        "refined": refined.reshape(b, length, NUM_COORDS_PER_RES, 3),
        "proto": proto,
        "distogram_weights": weights,
        "cloud_mask": cloud_mask,
        "distogram_logits": logits,
    }


def make_e2e_loss_fn(model_apply_fn=None):
    """Build the e2e structure loss around any model apply function — ONE
    loss construction shared by the replicated and sequence-parallel paths
    (parallel/train.py sp_e2e_loss_fn)."""

    def loss_fn(params, ecfg: E2EConfig, batch, rng):
        """Kabsch-aligned RMSD + dispersion loss on one microbatch
        (reference train_end2end.py:172-176).

        batch: {"seq": (b, L) int, "mask": (b, L) bool,
                "coords": (b, L, 14, 3) ground-truth atom cloud,
                optional "atom_mask": (b, L, 14) bool — per-atom resolution
                (sidechainnet zero-pads unresolved atoms; without this they
                would enter the loss as ground truth at the origin)}.
        """
        out = predict_structure(
            params, ecfg, batch["seq"], mask=batch.get("mask"), rng=rng,
            msa=batch.get("msa"), msa_mask=batch.get("msa_mask"),
            embedds=batch.get("embedds"), model_apply_fn=model_apply_fn,
        )
        b, length = batch["seq"].shape
        num_atoms = length * NUM_COORDS_PER_RES
        w = out["cloud_mask"].reshape(b, num_atoms).astype(jnp.float32)
        atom_mask = batch.get("atom_mask")
        if atom_mask is not None:
            w = w * atom_mask.reshape(b, num_atoms).astype(jnp.float32)

        with scope("kabsch_loss"):
            pred = jnp.transpose(out["refined"].reshape(b, num_atoms, 3), (0, 2, 1))
            true = jnp.transpose(
                jnp.asarray(batch["coords"], jnp.float32).reshape(b, num_atoms, 3),
                (0, 2, 1),
            )
            pred_aligned, true_centered = kabsch(pred, true, weights=w)

            sq = jnp.sum(jnp.square(pred_aligned - true_centered), axis=-2)  # (b, A)
            denom = jnp.maximum(jnp.sum(w, axis=-1), 1.0)
            rmsd = jnp.sqrt(jnp.sum(sq * w, axis=-1) / denom)  # (b,)

        # dispersion penalty over UNCENSORED pairs only: censored pairs
        # (weight hard-zeroed by center_distogram for beyond-last-bucket
        # predictions) would add a huge ~1/eps constant with exactly zero
        # gradient, drowning the RMSD signal in the reported loss
        with scope("dispersion"):
            dw = out["distogram_weights"]
            valid = (dw > 0).astype(jnp.float32)
            per_pair = jnp.abs(1.0 / (dw + ecfg.weights_eps) - 1.0) * valid
            dispersion = jnp.sum(per_pair) / jnp.maximum(jnp.sum(valid), 1.0)
        return jnp.mean(rmsd) + ecfg.dispersion_weight * dispersion

    return loss_fn


# the default (replicated-model) e2e loss
e2e_loss_fn = make_e2e_loss_fn()


def e2e_params_init(key, ecfg: E2EConfig):
    """Joint (trunk, refiner) param pytree — the params-only init
    inference entry points use (no optimizer moments allocated)."""
    k1, k2 = jax.random.split(key)
    return {
        "model": alphafold2_init(k1, ecfg.model),
        "refiner": refiner_init(k2, ecfg.refiner),
    }


def e2e_train_state_init(key, ecfg: E2EConfig, tcfg):
    """TrainState over the joint (trunk, refiner) param pytree."""
    from alphafold2_tpu.ops.quant import reject_quant_training
    from alphafold2_tpu.training.harness import make_optimizer

    # int8 weights are the inference-only serving arm (ops/quant.py)
    reject_quant_training(ecfg, "e2e_train_state_init")
    params = e2e_params_init(key, ecfg)
    opt = make_optimizer(tcfg)
    return {"params": params, "opt_state": opt.init(params), "step": jnp.zeros((), jnp.int32)}
