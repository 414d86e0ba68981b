"""Refinement CLI: PDB in -> relaxed PDB out.

Reference parity: `scripts/refinement.py` (pose<->pdb converters + an
unimplemented FastRelax hook). This CLI actually runs: PyRosetta FastRelax
when installed, otherwise the jax_relax geometric fallback
(alphafold2_tpu/refinement.py).

Usage: python scripts/refinement.py input.pdb output.pdb [--iters 200]
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
# host-side tool: CPU by design, set before jax is imported
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402

from alphafold2_tpu.geometry.pdb import coords_to_pdb, parse_pdb  # noqa: E402
from alphafold2_tpu.refinement import pyrosetta_available, run_fast_relax  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("input")
    ap.add_argument("output")
    ap.add_argument("--iters", type=int, default=200)
    args = ap.parse_args()

    structure = parse_pdb(args.input).select_atoms(("N", "CA", "C"))
    # keep only residues with a COMPLETE N/CA/C backbone: partial residues
    # (common in experimental PDBs) would misalign every later atom triplet
    by_res = {}
    for a in structure.atoms:
        by_res.setdefault((a.chain_id, a.res_seq), {})[a.name] = a
    complete = [
        k for k, atoms in sorted(by_res.items()) if {"N", "CA", "C"} <= set(atoms)
    ]
    dropped = len(by_res) - len(complete)
    if dropped:
        print(f"warning: dropping {dropped} residue(s) with incomplete backbone")
    from alphafold2_tpu.geometry.pdb import THREE_TO_ONE

    seq = "".join(THREE_TO_ONE.get(by_res[k]["CA"].res_name, "X") for k in complete)
    coords = np.asarray(
        [by_res[k][n].xyz for k in complete for n in ("N", "CA", "C")]
    )
    # peptide bonds exist only between same-chain residues with consecutive
    # numbering — chain breaks and gaps (incl. residues dropped above) must
    # not be welded by the relaxation
    peptide_mask = np.asarray(
        [
            complete[i][0] == complete[i + 1][0]
            and complete[i + 1][1] == complete[i][1] + 1
            for i in range(len(complete) - 1)
        ],
        bool,
    )
    n_breaks = int((~peptide_mask).sum())
    if n_breaks:
        print(f"note: {n_breaks} chain break(s)/gap(s) excluded from relaxation")
    backend = "pyrosetta FastRelax" if pyrosetta_available() else "jax_relax fallback"
    print(f"relaxing {len(seq)} residues via {backend}")
    relaxed = run_fast_relax(
        np.asarray(coords), seq, iters=args.iters, peptide_mask=peptide_mask
    )
    # carry per-residue confidence (B-factors, predict.py convention)
    # through relaxation — relaxation moves atoms, not confidence
    bfactors = np.asarray([by_res[k]["CA"].bfactor for k in complete])
    coords_to_pdb(args.output, relaxed, sequence=seq,
                  bfactors=bfactors if bfactors.any() else None)
    print(f"wrote {args.output}")


if __name__ == "__main__":
    main()
