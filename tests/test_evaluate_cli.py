"""scripts/evaluate.py — predicted-vs-truth structure scoring CLI."""

import json
import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRUTH = os.path.join(REPO, "tests", "data", "1h22_protein_chain_1.pdb")


def run_cli(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "evaluate.py"), *argv],
        capture_output=True, text=True, env=env,
    )


def test_identity_scores_perfect():
    out = run_cli(TRUTH, TRUTH)
    assert out.returncode == 0, out.stderr[-400:]
    r = json.loads(out.stdout)
    assert r["rmsd"] == 0.0 and r["tm_score"] == 1.0 and r["gdt_ts"] == 1.0
    assert r["n_residues"] == 482


def test_rigid_motion_plus_noise_recovered(tmp_path):
    from alphafold2_tpu.geometry.pdb import parse_pdb, write_pdb

    s = parse_pdb(TRUTH)
    rng = np.random.RandomState(0)
    q, _ = np.linalg.qr(rng.randn(3, 3))
    if np.linalg.det(q) < 0:
        q[:, 0] *= -1
    for a in s.atoms:
        a.xyz = q @ a.xyz + rng.randn(3) * 0.3 + np.array([5.0, -3.0, 2.0])
    moved = str(tmp_path / "moved.pdb")
    write_pdb(moved, s)

    out = run_cli(moved, TRUTH)
    assert out.returncode == 0, out.stderr[-400:]
    r = json.loads(out.stdout)
    # alignment must recover the rotation/translation, leaving only the
    # injected 0.3-sigma noise
    assert 0.2 < r["rmsd"] < 0.8, r
    assert r["tm_score"] > 0.95 and r["hand"] == "direct"


def test_mirror_scored_on_better_hand(tmp_path):
    from alphafold2_tpu.geometry.pdb import parse_pdb, write_pdb

    s = parse_pdb(TRUTH)
    for a in s.atoms:
        a.xyz = a.xyz * np.array([1.0, 1.0, -1.0])
    mirrored = str(tmp_path / "mirror.pdb")
    write_pdb(mirrored, s)

    out = run_cli(mirrored, TRUTH)
    assert out.returncode == 0, out.stderr[-400:]
    r = json.loads(out.stdout)
    assert r["hand"] == "mirrored" and r["rmsd"] < 0.01, r


def test_partial_coverage_normalized_by_truth_length(tmp_path):
    # a perfect prediction of only the first 100 residues must NOT score
    # TM/GDT ~1.0: headline numbers normalize by the truth chain length
    from alphafold2_tpu.geometry.pdb import PdbStructure, parse_pdb, write_pdb

    s = parse_pdb(TRUTH)
    partial = PdbStructure([a for a in s.atoms if a.res_seq <= 100])
    moved = str(tmp_path / "partial.pdb")
    write_pdb(moved, partial)

    out = run_cli(moved, TRUTH)
    assert out.returncode == 0, out.stderr[-400:]
    r = json.loads(out.stdout)
    assert r["rmsd"] < 0.01  # the covered part is exact
    assert r["coverage_truth"] < 0.25
    assert r["tm_score"] < 0.3 and r["gdt_ts"] < 0.3, r


def test_bad_chain_fails_loudly():
    out = run_cli(TRUTH, TRUTH, "--chain", "Z")
    assert out.returncode != 0
    assert "no chain 'Z'" in out.stderr
