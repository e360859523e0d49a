"""Matrix products at a stated operand precision, for the references and
their controls. ``f32`` is the reference itself (float32 operands, float32
accumulation, ``highest`` matmul precision on a TPU). ``fp8`` (4 exponent
and 3 mantissa bits, one scale per tensor) rounds the OPERANDS of every
product the way hardware of that precision would and then multiplies
exactly: the nearest precision below the bfloat16 both configurations state,
put in the reference's place as the control. Rounding passes gradients
straight through."""

import jax
import jax.numpy as jnp

PRECISIONS = ("f32", "fp8")


def _straight_through(x, q):
    return x + jax.lax.stop_gradient(q - x)


def round_operand(x, precision):
    """``x`` rounded to ``precision``."""
    if precision == "f32":
        return x
    if precision == "fp8":
        # reduce_precision, not a pair of casts: XLA may drop a narrowing
        # and widening pair as excess precision, and on the TPU it does
        s = 224.0 / (jnp.max(jnp.abs(x)) + 1e-30)   # e4m3's largest is 240
        q = jax.lax.reduce_precision(x * s, 4, 3) / s
        return _straight_through(x, q)
    raise ValueError(f"unknown precision {precision!r}; one of {PRECISIONS}")


def matmul(x, w, precision):
    """``x [..., K] @ w [K, N]`` with operands rounded to ``precision``."""
    return jnp.matmul(round_operand(x, precision),
                      round_operand(w, precision),
                      precision=jax.lax.Precision.HIGHEST)


def einsum(spec, a, b, precision):
    return jnp.einsum(spec, round_operand(a, precision),
                      round_operand(b, precision),
                      precision=jax.lax.Precision.HIGHEST)
