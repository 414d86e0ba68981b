"""Structure evaluation CLI: predicted vs reference PDB -> RMSD / TM / GDT.

The reference computes these metrics only inside a manual notebook
(reference notebooks/structure_utils_tests.ipynb cells 10-20); this makes
the same comparison a one-liner. Structures are matched on their common
CA set (by residue number), Kabsch-aligned, and scored with the library
metrics (geometry/metrics.py — reference utils.py:563-624 parity).

Usage: python scripts/evaluate.py prediction.pdb truth.pdb [--chain A]
Prints one JSON line so runs can be collected into JSONL records.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def ca_map(structure):
    """residue number -> CA coordinate (filter chains BEFORE calling)."""
    out = {}
    for a in structure.atoms:
        if a.name == "CA" and a.res_seq not in out:
            out[a.res_seq] = a.xyz
    return out


def pick_chain(structure, wanted, label, path):
    chains = structure.chains()
    if not chains:
        raise SystemExit(f"no ATOM records in {label} file {path}")
    if wanted is None:
        return structure.select_chain(chains[0]), chains[0]
    if wanted not in chains:
        raise SystemExit(
            f"{label} file {path} has no chain {wanted!r} "
            f"(available: {', '.join(chains)})"
        )
    return structure.select_chain(wanted), wanted


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("prediction")
    ap.add_argument("truth")
    ap.add_argument("--chain", default=None,
                    help="chain of the TRUTH structure to score against "
                         "(default: first chain)")
    ap.add_argument("--pred-chain", default=None,
                    help="chain of the PREDICTION to score "
                         "(default: first chain)")
    args = ap.parse_args()

    # host-side tool: CPU by design, set before jax is imported
    os.environ["JAX_PLATFORMS"] = "cpu"

    from alphafold2_tpu.geometry import GDT, Kabsch, RMSD, TMscore
    from alphafold2_tpu.geometry.pdb import parse_pdb

    pred, pred_chain = pick_chain(
        parse_pdb(args.prediction), args.pred_chain, "prediction",
        args.prediction,
    )
    truth, truth_chain = pick_chain(
        parse_pdb(args.truth), args.chain, "truth", args.truth,
    )

    pmap, tmap = ca_map(pred), ca_map(truth)
    common = sorted(set(pmap) & set(tmap))
    if len(common) < 3:
        raise SystemExit(
            f"only {len(common)} common CA residues between "
            f"{args.prediction} ({len(pmap)}) and {args.truth} "
            f"({len(tmap)}) — residue numbering must correspond"
        )

    import jax.numpy as jnp

    P = jnp.asarray(np.stack([pmap[i] for i in common]).T)  # (3, N)
    T = jnp.asarray(np.stack([tmap[i] for i in common]).T)
    aligned, ref = Kabsch(P, T)
    # MDS-derived structures carry a reflection ambiguity the phi fix can
    # miss on CA-only traces: score the better hand, report which
    mirrored, ref_m = Kabsch(P * jnp.array([[1.0], [1.0], [-1.0]]), T)
    r_a = float(RMSD(aligned, ref)[0])
    r_m = float(RMSD(mirrored, ref_m)[0])
    if r_m < r_a:
        aligned, ref, hand = mirrored, ref_m, "mirrored"
    else:
        hand = "direct"

    # TM/GDT normalized by the TRUTH chain length (standard convention:
    # residues the prediction does not cover count as failures), so partial
    # predictions cannot score inflated headline numbers; RMSD is over the
    # aligned common set as usual
    n_truth = len(tmap)
    result = {
        "chains": f"{pred_chain}->{truth_chain}",
        "n_residues": len(common),
        "coverage_pred": round(len(common) / max(1, len(pmap)), 3),
        "coverage_truth": round(len(common) / max(1, n_truth), 3),
        "rmsd": round(float(RMSD(aligned, ref)[0]), 3),
        "tm_score": round(float(TMscore(aligned, ref, norm_len=n_truth)[0]), 4),
        "gdt_ts": round(float(GDT(aligned, ref, norm_len=n_truth)[0]), 4),
        "gdt_ha": round(
            float(GDT(aligned, ref, mode="HA", norm_len=n_truth)[0]), 4),
        "hand": hand,
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
