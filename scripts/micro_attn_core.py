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

`--causal` measures the decoder's core instead (`--shape B,n,h,dh,dv`,
default the language-model cell's 2,8192,32,192,128; q, k of
(B, n, h*dh) and v of (B, n, h*dv) bf16, no bias), through
`flash_attention(causal=True)`:

  xla            the XLA arm (`causal_blockwise_attention`, tiles of 1024)
  kernel         the Pallas causal form at the plan the shape gets
  kernel<q>x<k>  the same forced through kernel_qb = q, kernel_kb = k
  folded<q>x<k>  the same kernels one head a grid step on (B*h, n, d)
                 operands, with the transposes that layout costs

with the REQUIRED work (the causal half, n (n + 1) (dh + dv) flops a row
forward, 2.5x that backward) and max |gap| of out, dq, dk, dv against
the first arm.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _timed(fn, iters, *a):
    """(seconds a call over `iters` calls, the first call's, the output)."""
    import jax

    t0 = time.perf_counter()
    out = fn(*a)
    jax.block_until_ready(out)
    first = time.perf_counter() - t0
    jax.block_until_ready(fn(*a))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*a)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters, first, out


def causal(args, dev) -> int:
    import jax
    import jax.numpy as jnp

    from alphafold2_tpu.ops import flash_kernel
    from alphafold2_tpu.ops.flash import flash_attention

    B, n, h, dh, dv = (int(t) for t in (args.shape or "2,8192,32,192,128").split(","))
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    xq = jax.random.normal(ks[0], (B, n, h * dh), jnp.bfloat16)
    xk = jax.random.normal(ks[1], (B, n, h * dh), jnp.bfloat16)
    xv = jax.random.normal(ks[2], (B, n, h * dv), jnp.bfloat16)
    w = jax.random.normal(ks[3], (B, n, h * dv), jnp.bfloat16)
    scale = dh ** -0.5

    def core(arm):
        kw = {"use_kernel": arm != "xla"}
        if arm[6:]:  # kernel<q>x<k>, folded<q>x<k>
            kw["kernel_qb"], kw["kernel_kb"] = (int(t) for t in arm[6:].split("x"))

        def run(q, k, v):
            o = flash_attention(q.reshape(B, n, h, dh), k.reshape(B, n, h, dh),
                                v.reshape(B, n, h, dv), causal=True, scale=scale, **kw)
            return o.reshape(B, n, h * dv)

        is_folded = arm.startswith("folded")
        plan = None
        if arm != "xla" and hasattr(flash_kernel, "causal_plan"):  # the parent has none
            plan = flash_kernel.causal_plan(n, 1 if is_folded else h, dh, dv, 2,
                                            kw.get("kernel_qb"), kw.get("kernel_kb"))

        def folded(q, k, v):
            def fold(t, d):
                return t.reshape(B, n, h, d).transpose(0, 2, 1, 3).reshape(B * h, n, d)

            o = flash_kernel._causal_core(fold(q, dh), fold(k, dh), fold(v, dv),
                                          scale, dh, plan)
            return o.reshape(B, h, n, dv).transpose(0, 2, 1, 3).reshape(B, n, h * dv)

        return (folded if is_folded else run), plan

    rows_n = B * h
    flops_fwd = float(n) * (n + 1) * (dh + dv) * rows_n
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    ref = []
    print(f"device {dev.device_kind}; causal B={B} n={n} h={h} dh={dh} dv={dv}; "
          f"{rows_n} (batch, head) rows; tag {args.tag!r}")
    print(f"{'arm':<16} {'fwd us/row':>10} {'TF/s':>6} {'grad us/row':>11} {'TF/s':>6} "
          f"{'|out|':>8} {'|dq|':>8} {'|dk|':>8} {'|dv|':>8}  plan")
    for arm in (args.arms or "xla,kernel").split(","):
        rec = {"arm": arm, "tag": args.tag, "causal": True, "shape": [B, n, h, dh, dv],
               "device_kind": dev.device_kind, "iters": args.iters}
        try:
            run, plan = core(arm)
            if plan is not None:
                rec["plan"] = plan._asdict()
            loss = lambda q, k, v: jnp.sum(  # noqa: E731
                run(q, k, v).astype(jnp.float32) * w.astype(jnp.float32))
            tf, cf, out = _timed(jax.jit(run), args.iters, xq, xk, xv)
            tg, cg, grads = _timed(jax.jit(jax.grad(loss, (0, 1, 2))), args.iters,
                                   xq, xk, xv)
            got = [out] + list(grads)
            ref = ref or got
            gaps = [float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
                    for a, b in zip(got, ref)]
            rec.update(
                fwd_us_per_row=tf / rows_n * 1e6, grad_us_per_row=tg / rows_n * 1e6,
                fwd_tflops_required=flops_fwd / tf / 1e12,
                grad_tflops_required=3.5 * flops_fwd / tg / 1e12,
                fwd_s=tf, grad_s=tg, first_call_s=[cf, cg], gaps_vs_first_arm=gaps,
                finite=bool(all(jnp.all(jnp.isfinite(t.astype(jnp.float32))) for t in got)),
            )
            print(f"{arm:<16} {rec['fwd_us_per_row']:>10.1f} {rec['fwd_tflops_required']:>6.1f} "
                  f"{rec['grad_us_per_row']:>11.1f} {rec['grad_tflops_required']:>6.1f} "
                  + " ".join(f"{g:>8.1e}" for g in gaps)
                  + f"  {rec.get('plan', '')} finite={rec['finite']}")
        except Exception as e:  # an arm that does not compile is a reading too
            rec["error"] = f"{type(e).__name__}: {str(e)[:600]}"
            print(f"{arm:<16} not compiled / failed: {rec['error'][:300]}")
        with open(args.out, "a") as fh:
            fh.write(json.dumps(rec) + "\n")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--causal", action="store_true")
    ap.add_argument("--shape", default=None)
    ap.add_argument("--arms", default=None)
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--out", default="chiprun_out/micro_attn_core.jsonl")
    ap.add_argument("--tag", default="")
    ap.add_argument("--dry", action="store_true",
                    help="rehearse off the chip (tiny --shape): the times mean nothing")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from alphafold2_tpu.ops import flash_kernel
    from alphafold2_tpu.ops.flash import flash_attention

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.dry:
        print(f"needs a TPU, found {dev.platform!r}", file=sys.stderr)
        return 2
    if args.causal:
        return causal(args, dev)
    args.shape = args.shape or "96,1152,1152,8,64"
    args.arms = args.arms or "xla,stream384,stream1152,rows"
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
            tf, cf, out = _timed(f, args.iters, xq, xk, xv)
            tg, cg, grads = _timed(gfn, args.iters, xq, xk, xv)
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
