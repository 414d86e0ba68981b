"""Host-side Mosaic lowering check for the Pallas kernels.

`jax.export` with platforms=["tpu"] runs the full Pallas -> Mosaic
lowering for the TPU target on a CPU host — the stage where BlockSpec
shapes, layouts, scratch allocation, and dimension semantics are
validated — without a chip. Lowering is not compiling: the Mosaic -> TPU
binary step, which enforces VMEM limits and (8,128)/(16,128) tiling, runs
inside libtpu at on-chip compile time (`chip_smoke.py`'s kernel phase).
Run this after any kernel change, before spending chip time.

Usage: python scripts/check_mosaic_lowering.py
(the script pins the CPU platform and AF2_PALLAS_INTERPRET=0 itself —
the check is host-side by definition)
"""

from __future__ import annotations

import os
import sys

os.environ["AF2_PALLAS_INTERPRET"] = "0"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp


def main():
    from alphafold2_tpu.ops.flash_kernel import (
        flash_attention_lse,
        flash_attention_tpu,
    )
    from alphafold2_tpu.ops.sparse import SparseConfig
    from alphafold2_tpu.ops.sparse_kernel import block_sparse_attention_tpu

    checks = []

    # dense flash at the north-star chunk shape (self) and aligned-cross
    for name, (BH, i, j, dh) in (
        ("flash_self_1152", (256, 1152, 1152, 64)),
        ("flash_cross_aligned", (384 * 8, 3456, 128, 64)),
    ):
        q = jax.ShapeDtypeStruct((BH, i, dh), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((BH, j, dh), jnp.bfloat16)
        v = jax.ShapeDtypeStruct((BH, j, dh), jnp.bfloat16)
        bias = jax.ShapeDtypeStruct((BH, j), jnp.float32)

        def fwdbwd(q, k, v, bias, dh=dh):  # bind: checks run after the loop
            out, vjp = jax.vjp(
                lambda q, k, v: flash_attention_tpu(q, k, v, bias, dh ** -0.5),
                q, k, v,
            )
            return vjp(out)

        def lse(q, k, v, bias, dh=dh):
            return flash_attention_lse(q, k, v, bias, dh ** -0.5)

        checks.append((f"{name}_fwdbwd", fwdbwd, (q, k, v, bias)))
        checks.append((f"{name}_lse", lse, (q, k, v, bias)))

    # the streaming form forced to one 1152-row query block: an e2e run
    # forcing this died in compile on an earlier shared chip; the
    # lowering itself passes
    qw = jax.ShapeDtypeStruct((256, 1152, 64), jnp.bfloat16)
    bw = jax.ShapeDtypeStruct((256, 1152), jnp.float32)

    def fwdbwd_qb1152(q, k, v, bias):
        out, vjp = jax.vjp(
            lambda q, k, v: flash_attention_tpu(
                q, k, v, bias, 64 ** -0.5, qb=1152, kb=384
            ),
            q, k, v,
        )
        return vjp(out)

    checks.append(("flash_self_qb1152_fwdbwd", fwdbwd_qb1152, (qw, qw, qw, bw)))

    # block-sparse at its kernel-dispatch regime (n >= 4096)
    scfg = SparseConfig(block_size=128, max_seq_len=8192)
    sb, sn, sh, sdh = 1, 4096, 8, 64
    q4 = jax.ShapeDtypeStruct((sb, sn, sh, sdh), jnp.bfloat16)
    m2 = jax.ShapeDtypeStruct((sb, sn), jnp.bool_)

    def sparse_fwdbwd(q, k, v, mask):
        out, vjp = jax.vjp(
            lambda q, k, v: block_sparse_attention_tpu(q, k, v, scfg, mask),
            q, k, v,
        )
        return vjp(out)

    checks.append(("sparse_4096_fwdbwd", sparse_fwdbwd, (q4, q4, q4, m2)))

    failed = False
    for name, fn, args in checks:
        try:
            exp = jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)
            n_calls = exp.mlir_module().count("tpu_custom_call")
            assert n_calls > 0, "no tpu_custom_call in module — interpret leaked in"
            print(f"OK   {name}: Mosaic lowering passed ({n_calls} kernels)")
        except Exception as e:  # noqa: BLE001 - report and continue
            failed = True
            msg = str(e).splitlines()[0][:200]
            print(f"FAIL {name}: {type(e).__name__}: {msg}")
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
