"""Time ONE expert layer (ops/moe.py `moe_apply`, forward and backward
under a layer-like `jax.checkpoint`) at a cell's own widths and tokens,
for each block size of the expert loop and each held load asked for: what
the block rule (`ops.moe.block_rows_for`) is chosen from. The picks are
drawn here (a Gumbel top-k over skewed expert logits, the held experts'
offset bisected to the load asked for), so a load costs no router and one
compiled program serves every load of a (cell, block).

    python benchmarks/tools/moe_block_sweep.py [--dry] [--blocks 2048,4096,...]
        [--parent-moe PATH] <cell>:<held load>[,<held load>...] ...

`rule` among the blocks is the program's own rule. `--parent-moe PATH`
also times the `moe_apply` of another tree's `ops/moe.py` (block `parent`).
One line a (cell, block, load) to chiprun_out/records/moe_block_sweep.jsonl:
milliseconds a layer (the median of `--repeats` calls), rows walked, and
the device. A time from `--dry` (toy widths on the CPU) is not written.
"""
import argparse
import importlib.util
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import common  # noqa: E402
from common import log  # noqa: E402


def draw_picks(rng, n_tokens, top_k, n_experts, held, load, skew=0.55):
    """(n_tokens, top_k) distinct picks a token of which about `load` lie
    in `held`: the top-k of expert logits (normal, `skew` wide) plus Gumbel
    noise, the held experts' logits moved together until the count fits."""
    import numpy as np

    base = skew * rng.standard_normal(n_experts)
    noise = rng.gumbel(size=(n_tokens, n_experts))
    lo, hi = held
    is_held = (np.arange(n_experts) >= lo) & (np.arange(n_experts) < hi)
    low, high = -30.0, 30.0
    for _ in range(40):
        offset = 0.5 * (low + high)
        idx = np.argsort(-(base + offset * is_held + noise), axis=-1)[:, :top_k]
        count = int(is_held[idx].sum())
        low, high = (offset, high) if count < load else (low, offset)
    return idx.astype(np.int32)


def layer_grads(moe, n_experts, held):
    """jitted (experts, h, weights, cotangent, picks) -> value and gradients
    of a layer-like checkpoint around `moe.moe_apply` on the picks given
    (the value, so that the forward is there to be timed)."""
    import jax
    import jax.numpy as jnp

    def layer(experts, h, weights, idx):
        load = jnp.zeros((n_experts,), jnp.float32).at[idx.reshape(-1)].add(1.0)
        y, _ = moe.moe_apply({"experts": experts}, h, (idx, weights, load), held=held)
        return h + y

    def loss(experts, h, weights, ct, idx):
        out = jax.checkpoint(layer)(experts, h, weights, idx)
        return jnp.sum(out.astype(jnp.float32) * ct)

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cells", nargs="+", help="<workload>:<load>[,<load>...]")
    ap.add_argument("--blocks", default="2048,4096,8192,16384,rule")
    ap.add_argument("--parent-moe", default=None)
    ap.add_argument("--repeats", type=int, default=8)
    ap.add_argument("--dry", action="store_true")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from alphafold2_tpu.ops import moe

    device = common.require_tpu(1, args.dry)[0]
    rule = moe.block_rows_for
    sides = [(b, moe) for b in args.blocks.split(",")]
    if args.parent_moe:
        spec = importlib.util.spec_from_file_location("parent_moe", args.parent_moe)
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
        sides.append(("parent", parent))
    out_dir = os.path.join(common.ROOT, "chiprun_out", "records")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "moe_block_sweep.jsonl")

    for spec in args.cells:
        workload, loads = spec.split(":")
        _, _, config, traffic = common.load_cell(workload)
        cfg = common.module("builders", config["builder"]).build(config, args.dry)["cfg"]
        shape = traffic["dry"] if args.dry else traffic
        n_tokens = shape["batch"] * shape["length"]
        lo, hi = held = cfg.held
        d, f, top_k = cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts_per_tok
        n_experts = getattr(cfg, cfg.router_width_key)
        key = jax.random.PRNGKey(36)
        kg, ku, kd, kh, kw, kc = jax.random.split(key, 6)
        experts = {
            "gate": {"w": 0.02 * jax.random.normal(kg, (hi - lo, d, f), jnp.float32)},
            "up": {"w": 0.02 * jax.random.normal(ku, (hi - lo, d, f), jnp.float32)},
            "down": {"w": 0.02 * jax.random.normal(kd, (hi - lo, f, d), jnp.float32)}}
        h = jax.random.normal(kh, (n_tokens, d), cfg.compute_dtype)
        weights = jax.random.uniform(kw, (n_tokens, top_k), jnp.float32, 0.05, 0.3)
        ct = jax.random.normal(kc, (n_tokens, d), jnp.float32)
        rng = np.random.default_rng(36)
        picks = [draw_picks(rng, n_tokens, top_k, n_experts, held, int(load))
                 for load in loads.split(",")]
        for block, module in sides:
            block_rows = None
            if block not in ("rule", "parent"):
                block_rows = int(block)
                moe.block_rows_for = lambda *a, rows=block_rows: rows
            elif block == "rule":
                moe.block_rows_for = rule
                block_rows = rule(n_tokens, top_k, hi - lo, n_experts)
            step = layer_grads(module, n_experts, held)
            for idx in picks:
                held_rows = int(((idx >= lo) & (idx < hi)).sum())
                idx = jnp.asarray(idx)
                t0 = time.perf_counter()
                jax.block_until_ready(step(experts, h, weights, ct, idx))
                first = time.perf_counter() - t0
                times = []
                for _ in range(args.repeats):
                    t0 = time.perf_counter()
                    jax.block_until_ready(step(experts, h, weights, ct, idx))
                    times.append(time.perf_counter() - t0)
                line = {
                    "workload": workload, "block": block, "block_rows": block_rows,
                    "held": held_rows, "layer_ms": 1e3 * statistics.median(times),
                    "layer_ms_min": 1e3 * min(times), "first_call_s": first,
                    "device": device.device_kind, "repeats": args.repeats}
                if block_rows:
                    line["rows_walked"] = float(moe.rows_walked(held_rows, block_rows))
                log(json.dumps(line))
                if not args.dry:
                    with open(path, "a") as out:
                        out.write(json.dumps(line) + "\n")
        moe.block_rows_for = rule


if __name__ == "__main__":
    main()
