"""Microbenchmark: Pallas dense flash kernel vs XLA blockwise streaming.

Times `flash_attention` forward and forward+backward at the axial-attention
shapes the north-star workload produces (crop 384 -> 1152x1152 pair grid:
folded batch B=1152, seq n=1152, heads=8, dh=64), kernel vs XLA path.

Methodology matches bench.py: iterations run inside one jitted `lax.scan`
and the result is fetched before the clock stops, so the timing ends when
the device has finished. One process; run it alone on the chip.

Usage: python scripts/bench_kernels.py [--b 1152 --n 1152 --iters 4]
Prints one JSON line per (path, direction) with TFLOP/s.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def _time_scan(fn, args, iters):
    """Run fn(args) `iters` times in one jitted scan; return sec/iter.

    The carry perturbs the first argument each iteration (by a numerically
    negligible but compiler-opaque amount), so the body is NOT loop-
    invariant: without this, XLA's loop-invariant code motion would hoist
    the whole computation out of the scan and the timing would measure one
    iteration, not `iters`.
    """

    def body(c, _):
        first = args[0] + (c * 1e-30).astype(args[0].dtype)
        out = fn(first, *args[1:])
        return jnp.sum(out.astype(jnp.float32)), None

    run = jax.jit(lambda: jax.lax.scan(body, jnp.float32(0.0), None, length=iters)[0])
    np.asarray(run())  # compile + warmup, fetched
    t0 = time.perf_counter()
    np.asarray(run())
    return (time.perf_counter() - t0) / iters


def bench(B, n, h, dh, iters, dtype, use_kernel, grad, key_frac_masked=0.0,
          qb=None, kb=None):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, n, h, dh), dtype)
    k = jax.random.normal(ks[1], (B, n, h, dh), dtype)
    v = jax.random.normal(ks[2], (B, n, h, dh), dtype)
    bias = jnp.zeros((B, n), jnp.float32)
    if key_frac_masked:
        nm = int(n * key_frac_masked)
        bias = bias.at[:, n - nm:].set(float("-inf"))

    from alphafold2_tpu.ops.flash import flash_attention

    def fwd(q, k, v):
        return flash_attention(
            q, k, v, bias, use_kernel=use_kernel,
            kernel_qb=qb, kernel_kb=kb,
        )

    if grad:
        def fn(q, k, v):
            loss, grads = jax.value_and_grad(
                lambda q, k, v: jnp.sum(fwd(q, k, v).astype(jnp.float32) ** 2),
                argnums=(0, 1, 2),
            )(q, k, v)
            return loss + sum(jnp.sum(g.astype(jnp.float32)) for g in grads)
    else:
        fn = fwd

    sec = _time_scan(fn, (q, k, v), iters)
    # model FLOPs: QK^T + AV = 2 * 2 * B*h*n*n*dh; backward ~ 2.5x fwd
    fwd_flops = 4 * B * h * n * n * dh
    flops = fwd_flops * (3.5 if grad else 1.0)
    return sec, flops / sec / 1e12


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--b", type=int, default=1152)
    ap.add_argument("--n", type=int, default=1152)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--dh", type=int, default=64)
    ap.add_argument("--iters", type=int, default=4)
    ap.add_argument("--masked", type=float, default=0.0)
    ap.add_argument("--paths", default="kernel,xla")
    ap.add_argument("--dirs", default="fwd,grad")
    ap.add_argument("--qb", type=int, default=None,
                    help="kernel query block (default: pick_block)")
    ap.add_argument("--kb", type=int, default=None,
                    help="kernel key block (default: pick_block)")
    args = ap.parse_args()

    from alphafold2_tpu.compile_cache import enable_compile_cache
    from alphafold2_tpu.ops.core import pallas_interpret

    enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu" or pallas_interpret():
        # a kernel microbenchmark off the chip, or with the kernel
        # interpreted, measures nothing a user runs
        raise SystemExit(
            f"bench_kernels: needs a TPU with compiled Pallas kernels; JAX "
            f"found platform {dev.platform!r}, pallas_interpret()="
            f"{pallas_interpret()}")
    dtype = jnp.bfloat16
    paths = args.paths.split(",")
    for path in paths:
        use_kernel = path == "kernel"
        for d in args.dirs.split(","):
            grad = d == "grad"
            sec, tflops = bench(
                args.b, args.n, args.heads, args.dh, args.iters,
                dtype, use_kernel, grad, args.masked,
                qb=args.qb, kb=args.kb,
            )
            blocks = (  # qb/kb only affect the kernel path
                f"_qb{args.qb or 'auto'}_kb{args.kb or 'auto'}"
                if use_kernel and (args.qb or args.kb) else ""
            )
            print(json.dumps({
                "path": path, "dir": d,
                "shape": f"B{args.b}_n{args.n}_h{args.heads}_dh{args.dh}"
                         + blocks,
                "sec_per_iter": round(sec, 4),
                "model_tflops_per_sec": round(tflops, 1),
                "platform": dev.platform,
                "device_kind": dev.device_kind,
            }), flush=True)


if __name__ == "__main__":
    main()
