"""Matrix products of the plain references at a stated precision.

The precision is carried out explicitly, so that it means the same on every
backend: an f32 operand is split into bfloat16 pieces by rounding its bits
to the upper 16 (a compiler may drop an f32 -> bf16 -> f32 round trip, but
not integer arithmetic on the bits), and every product of two pieces is
exact in f32.

- ``highest``: the f32 product (XLA's HIGHEST, six bfloat16 passes on a TPU);
- ``high``: three passes, ``hi·hi + hi·lo + lo·hi`` (XLA's HIGH on a TPU);
- ``bfloat16``: one pass over operands rounded to bfloat16, f32 accumulation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "high", "bfloat16")
_HIGHEST = jax.lax.Precision.HIGHEST


def _bf16(x):
    """``x`` rounded to the nearest bfloat16 (ties to even), kept in f32."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000), jnp.float32)


def _split(x):
    hi = _bf16(x)
    return hi, _bf16(x - hi)


def dot(a, b, precision: str):
    if precision == "highest":
        return jnp.matmul(a, b, precision=_HIGHEST)
    a_hi, a_lo = _split(a)
    b_hi, b_lo = _split(b)
    out = jnp.matmul(a_hi, b_hi, precision=_HIGHEST)
    if precision == "bfloat16":
        return out
    if precision == "high":
        return out + (jnp.matmul(a_hi, b_lo, precision=_HIGHEST)
                      + jnp.matmul(a_lo, b_hi, precision=_HIGHEST))
    raise ValueError(f"unknown precision {precision!r}; expected one of {PRECISIONS}")
