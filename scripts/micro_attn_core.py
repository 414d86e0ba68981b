"""Micro-measurement of the pair axial attention's core on the chip.

One batch chunk as the training step sees it (q, k, v of
(96, 1152, 8, 64) bf16 cut from (B, n, h*dh) projections, a key bias),
forward and `jax.grad`, through each arm of `ops/flash.py
flash_attention`:

  xla          the XLA streaming arm as the step runs it (tile 2^26, remat)
  stream384    the Pallas streaming form at `pick_block`'s 384 / 384
  stream1152   the streaming form forced to one 1152 / 1152 block
  rows         the whole-row form at the chunk `rows_plan` picks
  rows<r>      the whole-row form at r query rows a chunk

Prints a table (us a (batch, head) row, TFLOP/s of the two dots'
4*i*j*dh flops a row forward, 2.5x that backward) and appends one JSON
line an arm to chiprun_out/micro_attn_core.jsonl. TPU only: a CPU time
is not a device number.

    chiprun -- python scripts/micro_attn_core.py [--shape B,i,j,h,dh] [--arms a,b]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="96,1152,1152,8,64")
    ap.add_argument("--arms", default="xla,stream384,stream1152,rows")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--out", default="chiprun_out/micro_attn_core.jsonl")
    ap.add_argument("--tag", default="")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from alphafold2_tpu.ops import flash_kernel
    from alphafold2_tpu.ops.flash import flash_attention

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform!r}", file=sys.stderr)
        return 2
    B, i, j, h, dh = (int(t) for t in args.shape.split(","))
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    xq = jax.random.normal(ks[0], (B, i, h * dh), jnp.bfloat16)
    xk = jax.random.normal(ks[1], (B, j, h * dh), jnp.bfloat16)
    xv = jax.random.normal(ks[2], (B, j, h * dh), jnp.bfloat16)
    w = jax.random.normal(ks[3], (B, i, h * dh), jnp.bfloat16)
    keep = jax.random.uniform(ks[4], (B, j)) > 0.05
    bias = jnp.where(keep, 0.0, float("-inf")).astype(jnp.float32)
    scale = dh ** -0.5

    def core(arm):
        def run(q, k, v):
            q, k, v = (t.reshape(t.shape[0], t.shape[1], h, dh) for t in (q, k, v))
            if arm == "xla":
                o = flash_attention(q, k, v, bias, scale=scale, use_kernel=False,
                                    tile_elems=1 << 26, kv_block=2048)
            elif arm.startswith("stream"):
                blk = int(arm[len("stream"):])
                o = flash_attention(q, k, v, bias, scale=scale, use_kernel=True,
                                    kernel_qb=blk, kernel_kb=blk)
            elif arm == "rows":
                o = flash_attention(q, k, v, bias, scale=scale, use_kernel=True)
            else:  # rows<r>: the whole-row form at a forced chunk
                r = int(arm[len("rows"):])
                g = flash_kernel.rows_plan(i, j, h, dh)[0]
                o = flash_kernel._rows_core(
                    q.reshape(B, i, h * dh), k.reshape(B, j, h * dh),
                    v.reshape(B, j, h * dh), bias, scale, g, dh, r,
                ).reshape(B, i, h, dh)
            return o.reshape(B, i, h * dh)

        return run

    def timed(fn, *a):
        t0 = time.perf_counter()
        out = fn(*a)
        jax.block_until_ready(out)
        first = time.perf_counter() - t0
        jax.block_until_ready(fn(*a))
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = fn(*a)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.iters, first, out

    rows_n = B * h
    flops_fwd = 4.0 * i * j * dh * rows_n
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    ref = {}
    print(f"device {dev.device_kind}; shape B={B} i={i} j={j} h={h} dh={dh}; "
          f"{rows_n} (batch, head) rows")
    print(f"{'arm':<12} {'fwd us/row':>10} {'TF/s':>6} {'grad us/row':>11} "
          f"{'TF/s':>6} {'max|do|':>8} {'max|dq|':>8}  note")
    for arm in args.arms.split(","):
        rec = {"arm": arm, "tag": args.tag, "shape": [B, i, j, h, dh],
               "device_kind": dev.device_kind, "iters": args.iters}
        try:
            f = jax.jit(core(arm))
            loss = lambda q, k, v, f=core(arm): jnp.sum(  # noqa: E731
                f(q, k, v).astype(jnp.float32) * w.astype(jnp.float32))
            gfn = jax.jit(jax.grad(loss, (0, 1, 2)))
            tf, cf, out = timed(f, xq, xk, xv)
            tg, cg, grads = timed(gfn, xq, xk, xv)
            got = [out] + list(grads)
            if not ref:
                ref["v"] = got
            gaps = [float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
                    for a, b in zip(got, ref["v"])]
            rec.update(
                fwd_us_per_row=tf / rows_n * 1e6, grad_us_per_row=tg / rows_n * 1e6,
                fwd_tflops=flops_fwd / tf / 1e12,
                grad_tflops=3.5 * flops_fwd / tg / 1e12,
                fwd_s=tf, grad_s=tg, first_call_s=[cf, cg], gaps_vs_first_arm=gaps,
                finite=bool(all(jnp.all(jnp.isfinite(t.astype(jnp.float32))) for t in got)),
            )
            print(f"{arm:<12} {rec['fwd_us_per_row']:>10.2f} {rec['fwd_tflops']:>6.1f} "
                  f"{rec['grad_us_per_row']:>11.2f} {rec['grad_tflops']:>6.1f} "
                  f"{gaps[0]:>8.1e} {gaps[1]:>8.1e}  finite={rec['finite']}")
        except Exception as e:  # an arm that does not compile is a reading too
            rec["error"] = f"{type(e).__name__}: {str(e)[:600]}"
            print(f"{arm:<12} not compiled / failed: {rec['error'][:300]}")
        with open(args.out, "a") as fh:
            fh.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
