"""Extended real-data training (our framework only) past the parity run.

The 200-step comparison (scripts/losscurve_compare.py) proves trajectory
parity; this script continues OUR side from its saved final weights for
more optimizer steps on the same real-structure crop stream, tracking the
held-out distance-map correlation so the artifact can show the model
actually acquiring real structural signal (depth-1 dim-256, the reference
train_pre.py defaults). Appends to docs/losscurve/extended.jsonl.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))
sys.path.insert(0, os.path.join(REPO, "scripts"))

# host-side tool: CPU by design, set before jax is imported
os.environ["JAX_PLATFORMS"] = "cpu"

OUT = os.path.join(REPO, "docs", "losscurve")


def main(extra_steps=800, eval_every=100):
    import jax
    import torch

    from losscurve_compare import (
        heldout_distance_eval,
        load_proteins,
        make_batches,
    )
    from ref_loader import load_reference
    from alphafold2_tpu.models import Alphafold2Config
    from alphafold2_tpu.models.convert import convert_alphafold2
    from alphafold2_tpu.training import (
        TrainConfig,
        distogram_loss_fn,
        make_optimizer,
        make_train_step,
    )

    torch.manual_seed(0)
    ref = load_reference()
    model = ref.Alphafold2(dim=256, depth=1, heads=8, dim_head=64)
    cfg = Alphafold2Config(
        dim=256, depth=1, heads=8, dim_head=64, max_seq_len=2048
    )
    init_params = convert_alphafold2(model)
    leaves, treedef = jax.tree_util.tree_flatten(init_params)

    # resume from the furthest saved weights: extended_params.npz (a prior
    # run of this script) or the parity run's final_params.npz
    ext = os.path.join(OUT, "extended_params.npz")
    src = ext if os.path.exists(ext) else os.path.join(OUT, "final_params.npz")
    z = np.load(src)
    base_steps = int(z["steps"])
    print(f"resuming from {src} at step {base_steps}", flush=True)
    params = jax.tree_util.tree_unflatten(
        treedef, [z[f"leaf_{i}"] for i in range(len(leaves))]
    )

    proteins = load_proteins()
    # continue the SAME stream past the parity run's end
    batches = make_batches(proteins, base_steps + extra_steps)[base_steps:]

    def heldout(params):
        corr, mae, _, _ = heldout_distance_eval(params, cfg, proteins)
        return corr, mae

    tcfg = TrainConfig(learning_rate=3e-4, grad_accum=1)
    opt = make_optimizer(tcfg)
    state = {
        "params": params,
        # fresh Adam state: the compare run does not persist moments, and
        # a warm restart at step ~200 of a 3e-4 constant-lr run is benign
        "opt_state": opt.init(params),
        "step": np.asarray(base_steps, np.int32),
    }
    step = jax.jit(make_train_step(cfg, tcfg, loss_fn=distogram_loss_fn))

    path = os.path.join(OUT, "extended.jsonl")
    c0, m0 = heldout(state["params"])
    print(f"step {base_steps}: heldout corr={c0:.4f} mae={m0:.3f}", flush=True)
    with open(path, "a") as f:
        f.write(json.dumps({"step": base_steps, "corr": round(c0, 4),
                            "mae": round(m0, 3)}) + "\n")
        t0 = time.time()
        for i, (seq, mask, xyz) in enumerate(batches):
            batch = {"seq": seq[None], "mask": mask[None], "coords": xyz[None]}
            state, metrics = step(state, batch, None)
            done = base_steps + i + 1
            if done % eval_every == 0:
                corr, mae = heldout(state["params"])
                row = {"step": done, "loss": round(float(metrics["loss"]), 4),
                       "corr": round(corr, 4), "mae": round(mae, 3)}
                f.write(json.dumps(row) + "\n")
                f.flush()
                print(f"{row} ({time.time() - t0:.0f}s)", flush=True)

    done = base_steps + len(batches)
    trained = jax.tree_util.tree_leaves(state["params"])
    np.savez_compressed(
        ext, steps=done,
        stream=json.dumps([n for n, _, _ in proteins]),
        **{f"leaf_{i}": np.asarray(l) for i, l in enumerate(trained)},
    )
    print(json.dumps({"final_step": done, "saved": ext}))


if __name__ == "__main__":
    main()
