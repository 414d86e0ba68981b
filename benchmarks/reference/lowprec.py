"""The control: the reference computed in the nearest precision below the
one the configurations state (bfloat16), which is 8-bit floating point.
Every operand of every contraction is scaled so that its largest
magnitude sits at 240, rounded to float8 (e4m3) and scaled back; the
accumulation stays float32. `fp8` goes in the `q` slot of the reference."""
import jax.numpy as jnp


def fp8(t):
    scale = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30) / 240.0
    return (t / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale

