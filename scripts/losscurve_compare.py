"""Loss-curve comparison vs the reference on REAL protein data.

VERDICT r2 missing #1: the north star says "matching PyTorch-GPU loss
curves", and until now "trains correctly" rested on output/grad parity
tests alone — never on an actual optimization trajectory. This script
runs the SAME distogram-pretraining workload (reference
train_pre.py:72-102 semantics) through BOTH frameworks:

  * identical model config (dim 256, depth 1, heads 8, dim_head 64 —
    the reference train_pre.py:59-64 defaults);
  * identical initial weights (the torch model's random init converted
    into our pytrees via models/convert.py — the parity-test machinery);
  * identical data: random crops of real experimental structures
    (RCSB 1h22 chain A, acetylcholinesterase — vendored at
    tests/data/1h22_protein_chain_1.pdb — plus RCSB 4k77 when a second
    source is available), N-atom coordinates bucketized exactly like
    get_bucketed_distance_matrix (reference train_pre.py:35-40);
  * identical optimization: Adam(lr=3e-4), one optimizer step per batch
    (the reference's GRADIENT_ACCUMULATE_EVERY sums losses without
    rescaling — running accum=1 on both sides compares the same
    effective step without replicating that quirk).

sidechainnet (the reference's dataset) cannot download in this
environment (zero egress), so the real-data stream is built from the
vendored experimental structures instead: same kind of data (real
backbone coordinates + real sequences), same label construction.

Outputs docs/losscurve/{losses.jsonl, LOSSCURVE.md, losscurve.png}.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))
sys.path.insert(0, os.path.join(REPO, "scripts"))

# torch-reference parity + evals: CPU by design, set before jax is imported
os.environ["JAX_PLATFORMS"] = "cpu"

CROP = 128
REF_4K77 = "/root/reference/notebooks/data/4k77_protein.pdb"
VENDORED_4K77 = os.path.join(REPO, "tests", "data", "4k77_n_coords.npz")


def load_proteins():
    """-> list of (name, seq_tokens (L,), n_coords (L, 3)) real structures."""
    from alphafold2_tpu.constants import aa_to_tokens
    from alphafold2_tpu.geometry.pdb import parse_pdb

    proteins = []

    def add_from_pdb(name, path, chain=None):
        s = parse_pdb(path)
        if chain:
            s = s.select_chain(chain)
        seq = s.sequence()
        n = s.select_atoms(["N"]).coords()
        if len(seq) != len(n):
            raise ValueError(f"{name}: {len(seq)} residues vs {len(n)} N atoms")
        proteins.append((name, aa_to_tokens(seq), np.asarray(n, np.float32)))

    add_from_pdb("1h22", os.path.join(REPO, "tests", "data",
                                      "1h22_protein_chain_1.pdb"))

    # second real structure: derive once from the reference checkout's
    # public RCSB data file and vendor the ARRAYS (sequence + N coords)
    # so later rounds don't depend on /root/reference being present
    if os.path.exists(VENDORED_4K77):
        z = np.load(VENDORED_4K77)
        proteins.append(("4k77", z["tokens"], z["n_coords"]))
    elif os.path.exists(REF_4K77):
        add_from_pdb("4k77", REF_4K77)
        name, tokens, coords = proteins[-1]
        np.savez_compressed(VENDORED_4K77, tokens=tokens, n_coords=coords)
    return proteins


def make_batches(proteins, steps, crop=CROP, seed=42):
    """Fixed stream of (seq (1,crop) int32, mask (1,crop) bool,
    coords (1,crop,3) f32) crops, identical for both frameworks."""
    rng = np.random.RandomState(seed)
    batches = []
    for i in range(steps):
        name, tokens, coords = proteins[i % len(proteins)]
        start = rng.randint(0, len(tokens) - crop + 1)
        batches.append((
            tokens[None, start:start + crop].astype(np.int32),
            np.ones((1, crop), bool),
            coords[None, start:start + crop],
        ))
    return batches


# Fixed eval window at residues [200, 328) of proteins[0] (1h22). NOTE:
# this is NOT a held-out window — training crops start uniformly in
# [0, len-crop] of the same protein, so pairs inside it are trained on
# constantly; the metric is train-set recall (the model memorizing real
# structure it saw), not generalization. Round 3 mislabeled it; the
# honest zero-overlap eval (train on 4k77 only, evaluate on 1h22, a
# different protein) lives in scripts/generalization_run.py.
HELDOUT_START = 200


def heldout_distance_eval(params, cfg, proteins, crop=CROP,
                          start=HELDOUT_START, protein_index=0):
    """Distance-map metrics on proteins[protein_index]: (corr, mae,
    true_d, pred_d) over the distogram's expressible 2-20 A range. ONE
    definition shared by the artifact renderer, the extended-training
    eval trace, and the generalization run so they measure the same
    quantity. Whether the window is held out depends on the TRAINING
    stream the caller used — see the HELDOUT_START note above."""
    import jax
    import jax.numpy as jnp

    from alphafold2_tpu.geometry import center_distogram
    from alphafold2_tpu.models import alphafold2_apply

    name, tokens, coords = proteins[protein_index]
    seq = tokens[None, start:start + crop].astype(np.int32)
    true_d = np.linalg.norm(
        coords[start:start + crop, None] - coords[None, start:start + crop],
        axis=-1,
    )
    logits = alphafold2_apply(
        params, cfg, seq, None, mask=jnp.ones_like(jnp.asarray(seq), bool)
    )
    probs = jax.nn.softmax(np.asarray(logits, np.float32), axis=-1)
    dist, _ = center_distogram(probs, center="mean")
    pred_d = np.asarray(dist)[0]
    sel = (true_d > 2) & (true_d < 20) & ~np.eye(crop, dtype=bool)
    corr = float(np.corrcoef(true_d[sel], pred_d[sel])[0, 1])
    mae = float(np.abs(true_d[sel] - pred_d[sel]).mean())
    return corr, mae, true_d, pred_d


def run_torch(batches, model):
    """The reference training loop verbatim (train_pre.py:66-102,
    GRADIENT_ACCUMULATE_EVERY=1): Adam(3e-4), N-atom distance labels via
    bucketize(linspace(2, 20, 37)[:-1]), cross-entropy ignore -100."""
    import torch
    import torch.nn.functional as F
    from torch.optim import Adam

    optim = Adam(model.parameters(), lr=3e-4)
    boundaries = torch.linspace(2, 20, steps=37)
    losses = []
    t0 = time.time()
    for i, (seq, mask, coords) in enumerate(batches):
        seq_t = torch.from_numpy(seq).long()
        mask_t = torch.from_numpy(mask)
        coords_t = torch.from_numpy(coords)
        dist = torch.cdist(coords_t, coords_t, p=2)
        labels = torch.bucketize(dist, boundaries[:-1])
        labels.masked_fill_(~(mask_t[:, :, None] & mask_t[:, None, :]), -100)

        distogram = model(seq_t, mask=mask_t)
        loss = F.cross_entropy(
            distogram.permute(0, 3, 1, 2), labels, ignore_index=-100
        )
        loss.backward()
        optim.step()
        optim.zero_grad()
        losses.append(float(loss.item()))
        if i % 20 == 0:
            print(f"  torch step {i}: loss={losses[-1]:.4f} "
                  f"({time.time() - t0:.0f}s)", flush=True)
    return losses


def run_jax(batches, params, cfg, return_state=False):
    import jax

    from alphafold2_tpu.training import (
        TrainConfig,
        distogram_loss_fn,
        make_optimizer,
        make_train_step,
    )

    tcfg = TrainConfig(learning_rate=3e-4, grad_accum=1)
    opt = make_optimizer(tcfg)
    state = {
        "params": params,
        "opt_state": opt.init(params),
        "step": np.zeros((), np.int32),
    }
    step = jax.jit(make_train_step(cfg, tcfg, loss_fn=distogram_loss_fn))
    losses = []
    t0 = time.time()
    for i, (seq, mask, coords) in enumerate(batches):
        batch = {
            "seq": seq[None],  # leading grad-accum axis of 1
            "mask": mask[None],
            "coords": coords[None],
        }
        state, metrics = step(state, batch, None)
        losses.append(float(metrics["loss"]))
        if i % 20 == 0:
            print(f"  jax step {i}: loss={losses[-1]:.4f} "
                  f"({time.time() - t0:.0f}s)", flush=True)
    return (losses, state) if return_state else losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--out", default=os.path.join(REPO, "docs", "losscurve"))
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    proteins = load_proteins()
    print(f"proteins: {[(n, len(t)) for n, t, _ in proteins]}", flush=True)
    batches = make_batches(proteins, args.steps)

    # torch model first: its random init is the shared starting point
    import torch

    from ref_loader import load_reference
    from alphafold2_tpu.models import Alphafold2Config
    from alphafold2_tpu.models.convert import convert_alphafold2

    torch.manual_seed(0)
    ref = load_reference()
    model = ref.Alphafold2(dim=256, depth=1, heads=8, dim_head=64)
    cfg = Alphafold2Config(
        dim=256, depth=1, heads=8, dim_head=64, max_seq_len=2048
    )
    params = convert_alphafold2(model)

    print("running reference (torch CPU)...", flush=True)
    torch_losses = run_torch(batches, model)
    print("running alphafold2_tpu (jax)...", flush=True)
    jax_losses, jax_state = run_jax(batches, params, cfg, return_state=True)

    # persist the final weights for scripts/losscurve_artifact.py (which
    # renders the distance maps) so it never retrains, plus the stream
    # fingerprint so a stale cache fails loudly there
    import jax as _jax

    leaves = [np.asarray(l) for l in
              _jax.tree_util.tree_leaves(jax_state["params"])]
    np.savez_compressed(
        os.path.join(args.out, "final_params.npz"),
        steps=args.steps,
        stream=json.dumps([n for n, _, _ in proteins]),
        **{f"leaf_{i}": l for i, l in enumerate(leaves)},
    )

    with open(os.path.join(args.out, "losses.jsonl"), "w") as f:
        for i, (tl, jl) in enumerate(zip(torch_losses, jax_losses)):
            f.write(json.dumps({"step": i, "torch": round(tl, 6),
                                "jax": round(jl, 6)}) + "\n")

    d = np.abs(np.array(torch_losses) - np.array(jax_losses))
    summary = {
        "steps": args.steps,
        "torch_first": round(torch_losses[0], 4),
        "jax_first": round(jax_losses[0], 4),
        "torch_last": round(float(np.mean(torch_losses[-10:])), 4),
        "jax_last": round(float(np.mean(jax_losses[-10:])), 4),
        "max_abs_diff_first_25": round(float(d[:25].max()), 5),
        "max_abs_diff": round(float(d.max()), 5),
    }
    print(json.dumps(summary))
    with open(os.path.join(args.out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    return summary


if __name__ == "__main__":
    main()
