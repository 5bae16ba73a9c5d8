"""The matmul of a reference, at the precision the reference is run in.

"f32" is the reference proper: float32 operands, `highest` precision (on a
TPU anything less multiplies in bfloat16 passes). The others are the
CONTROL of "How correct is decided": the same mathematics one step below
what the configuration states, which the comparison has to fail.
"fp8" rounds both operands of every product to an e4m3 float (scaled by
the tensor's largest magnitude) and is the control for a bfloat16
configuration; "bf16" rounds them to bfloat16: the control of a float32
configuration, and for a bfloat16 one the second witness that shows how far
bfloat16 itself lies from float32. Rounding is straight-through for the gradient, so the
backward pass multiplies the rounded operands as a low-precision program
would.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _round_through(x, rounded):
    return x + jax.lax.stop_gradient(rounded - x)


def _fp8(x):
    """Rounded to a 4-bit exponent and a 3-bit mantissa, the tensor's
    largest magnitude scaled onto the format's largest finite value (240:
    reduce_precision keeps the top exponent for infinity, as IEEE does).
    reduce_precision and not a cast there and back: on the TPU the cast
    through float8_e4m3fn lost most of its rounding (PR 23: the "fp8"
    reference read 1.2% from float32 where this reads 13%)."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = 240.0 / amax
    return jax.lax.reduce_precision(x * scale, 4, 3) / scale


def operand(x, precision: str):
    x = x.astype(jnp.float32)
    if precision == "f32":
        return x
    if precision == "bf16":
        # reduce_precision and not a cast there and back, which XLA folds
        # away on the TPU (PR 23 read 2e-7 from such a "bfloat16" reference)
        return _round_through(x, jax.lax.reduce_precision(x, 8, 7))
    if precision == "fp8":
        return _round_through(x, _fp8(x))
    raise ValueError(f"unknown reference precision {precision!r}")


def store(x, precision: str):
    """A layer's output as a program of that precision keeps it: a
    bfloat16 program holds bfloat16 activations, an fp8 one fp8. It matters
    where the next operation is not a product: a max-pool over rounded
    values meets ties that float32 never does, and routes the gradient to
    another element."""
    return operand(x, precision)


def matmul(x, w, precision: str):
    return jnp.matmul(operand(x, precision), operand(w, precision),
                      precision=HIGHEST)


def einsum(spec: str, a, b, precision: str):
    return jnp.einsum(spec, operand(a, precision), operand(b, precision),
                      precision=HIGHEST)
