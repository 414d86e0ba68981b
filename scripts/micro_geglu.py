"""Micro-measurement of the GEGLU feed-forward block on the chip.

One call as the training step makes it (ops/feedforward.py
`feed_forward_apply` on (rows, dim) bf16, float32 weights of
`feed_forward_init`, mult 4), forward and `jax.grad`, through each arm:

  xla32          the XLA arm in float32 at HIGHEST precision: the reference
                 the gaps are read against
  xla            the XLA arm as the step runs it (bf16, chunks of --chunk rows)
  kernel         the Pallas kernel pair at the plan the shape gets
  kernel<t>x<l>  the same at t rows a grid step and l lanes a value/gate block

for each `--shapes` entry (rows,dim; default the pair stream's
1327104,256 and the MSA stream's 49152,256 of `train_e2e`). Prints a table
(ms a call; TFLOP/s of the block's 2 * rows * 3 * dim * hidden forward
flops, 3 x that for the gradient) with max |gap| of the output and of dx
against the first arm, and appends one JSON line an arm and shape to
--out. The gradient is of a loss linear in the output, so no arm needs
the output itself: the kernel arm's gradient is its backward kernel alone
(which recomputes the projection), the XLA arm's its backward and the
forward intermediates it reads. TPU only: a CPU time is not a device
number.

    python scripts/micro_geglu.py [--shapes r,d;r,d] [--arms a,b]   # on a TPU host
    JAX_PLATFORMS=cpu python scripts/micro_geglu.py --dry --shapes 300,128 --iters 1
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_ARMS = "xla32,xla,kernel1024x512,kernel512x512,kernel2048x512,kernel1024x1024,kernel2048x1024"
_BLOCKS = None  # the module's own (_TILE, _LANES), set in main


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


def block(arm, chunk):
    """The arm's (params, x) -> out."""
    import jax
    import jax.numpy as jnp

    from alphafold2_tpu.ops import geglu_kernel
    from alphafold2_tpu.ops.feedforward import feed_forward_apply

    if arm == "xla32":  # float32 products too (the TPU's default rounds
        # a float32 matmul's operands to bf16)
        def run(p, x):
            with jax.default_matmul_precision("highest"):
                return feed_forward_apply(p, x, dtype=jnp.float32, chunk=chunk,
                                          use_kernel=False)
        return run
    if arm == "xla":
        return lambda p, x: feed_forward_apply(p, x, dtype=jnp.bfloat16,
                                               chunk=chunk, use_kernel=False)
    # the blocks are read when the kernel is traced, right after this
    geglu_kernel._TILE, geglu_kernel._LANES = _BLOCKS
    if arm != "kernel":
        geglu_kernel._TILE, geglu_kernel._LANES = (
            int(t) for t in arm[len("kernel"):].split("x"))
    return lambda p, x: geglu_kernel.geglu_ff(p, x, jnp.bfloat16)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="1327104,256;49152,256")
    ap.add_argument("--arms", default=_ARMS)
    ap.add_argument("--mult", type=int, default=4)
    ap.add_argument("--chunk", type=int, default=32768,
                    help="the XLA arm's ff_chunk_size (the cell's preset)")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--out", default="chiprun_out/micro_geglu.jsonl")
    ap.add_argument("--tag", default="")
    ap.add_argument("--coherent", action="store_true",
                    help="rows sharing one direction, cotangents summing to 0 over rows")
    ap.add_argument("--dry", action="store_true",
                    help="rehearse off the chip (small --shapes): the times mean nothing")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from alphafold2_tpu.ops import geglu_kernel
    from alphafold2_tpu.ops.feedforward import feed_forward_init

    global _BLOCKS
    _BLOCKS = (geglu_kernel._TILE, geglu_kernel._LANES)
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.dry:
        print(f"needs a TPU, found {dev.platform!r}", file=sys.stderr)
        return 2
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    print(f"device {dev.device_kind}")
    for shape in args.shapes.split(";"):
        rows, dim = (int(t) for t in shape.split(","))
        hidden = args.mult * dim
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        params = feed_forward_init(ks[0], dim, args.mult)
        x = jax.random.normal(ks[1], (rows, dim), jnp.float32)
        w = jax.random.normal(ks[2], (rows, dim), jnp.float32)
        if args.coherent:
            # rows that share one direction and cotangents that cancel
            # over the rows, as a trunk's pair rows and their gradient do
            x = 0.3 * x + jax.random.normal(ks[0], (1, dim))
            w = w - jnp.mean(w, axis=0, keepdims=True)
        x = x.astype(jnp.bfloat16)
        flops_fwd = 2.0 * rows * 3 * dim * hidden
        ref = {}
        print(f"rows={rows} dim={dim} hidden={hidden}")
        print(f"{'arm':<16} {'fwd ms':>8} {'TF/s':>6} {'grad ms':>8} {'TF/s':>6} "
              f"{'max|out|':>9} {'max|dx|':>9}")
        for arm in args.arms.split(","):
            rec = {"arm": arm, "tag": args.tag, "rows": rows, "dim": dim,
                   "hidden": hidden, "device_kind": dev.device_kind,
                   "iters": args.iters}
            try:
                fn = block(arm, args.chunk)
                f = jax.jit(fn)
                # w is an argument: a constant of that size is folded into
                # the executable and takes minutes to compile
                loss = lambda p, x, w, fn=fn: jnp.sum(  # noqa: E731
                    fn(p, x).astype(jnp.float32) * w)
                gfn = jax.jit(jax.grad(loss, (0, 1)))
                tf, cf, out = _timed(f, args.iters, params, x)
                tg, cg, (gp, gx) = _timed(gfn, args.iters, params, x, w)
                got = [out, gx]
                got = got + jax.tree_util.tree_leaves(gp)
                if not ref:
                    ref["v"] = got
                gaps = [float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                              - b.astype(jnp.float32))))
                        for a, b in zip(got, ref["v"])]
                # the weights' gradients against the first arm's (proj_in
                # b, w; proj_out b, w): norm of the difference, and the gap
                # of the norms, each over the first arm's norm
                rec["param_grad_gaps"] = [
                    [float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)),
                     float(abs(jnp.linalg.norm(a) - jnp.linalg.norm(b))
                           / jnp.linalg.norm(b))]
                    for a, b in zip(got[2:], ref["v"][2:])]
                leaves = [out, gx] + jax.tree_util.tree_leaves(gp)
                rec.update(
                    fwd_ms=tf * 1e3, grad_ms=tg * 1e3,
                    fwd_tflops=flops_fwd / tf / 1e12,
                    grad_tflops=3 * flops_fwd / tg / 1e12,
                    first_call_s=[cf, cg], gaps_vs_first_arm=gaps,
                    finite=bool(all(jnp.all(jnp.isfinite(t.astype(jnp.float32)))
                                    for t in leaves)),
                )
                print(f"{arm:<16} {rec['fwd_ms']:>8.2f} {rec['fwd_tflops']:>6.1f} "
                      f"{rec['grad_ms']:>8.2f} {rec['grad_tflops']:>6.1f} "
                      f"{gaps[0]:>9.2e} {gaps[1]:>9.2e}  finite={rec['finite']}  "
                      f"dW gaps {[[round(v, 5) for v in g] for g in rec['param_grad_gaps']]}",
                      flush=True)
            except Exception as e:  # an arm that does not compile is a reading too
                rec["error"] = f"{type(e).__name__}: {str(e)[:600]}"
                print(f"{arm:<16} failed: {rec['error'][:300]}", flush=True)
            with open(args.out, "a") as fh:
                fh.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
