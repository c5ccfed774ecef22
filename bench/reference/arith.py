"""Squared Euclidean distances at a stated contraction precision."""
from __future__ import annotations

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "high")
_HIGHEST = jax.lax.Precision.HIGHEST


def _bf16_head(x):
    """x with its mantissa cut to bfloat16's 8 bits (toward zero), kept as
    float32. Done on the bits, so no compiler can fold it away."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)


def _split(x):
    """x = hi + lo + r with hi and lo bfloat16 values, |r| < 2⁻¹⁶·|x|."""
    hi = _bf16_head(x)
    return hi, _bf16_head(x - hi)


def _dot(a, b):
    # operands hold bfloat16 values: every product is exact in float32
    return jnp.dot(a, b.T, precision=_HIGHEST,
                   preferred_element_type=jnp.float32)


def cross(X, Y, precision: str):
    """X·Yᵀ for float32 X (n, d) and Y (m, d), as (n, m) float32.

    ``"high"`` is three bfloat16 passes, hi·hi + hi·lo + lo·hi, with the
    float32 split into bfloat16 words by truncation: what a TPU's
    ``Precision.HIGH`` computes, and the same on any backend.
    """
    if precision == "highest":
        return _dot(X, Y)
    if precision == "high":
        xh, xl = _split(X)
        yh, yl = _split(Y)
        return _dot(xh, yh) + (_dot(xh, yl) + _dot(xl, yh))
    raise ValueError(f"unknown precision {precision!r}; one of {PRECISIONS}")


def sq_norms(X):
    """‖x‖² per row: d(x, e0) for the all-zero auxiliary vector e0."""
    return jnp.sum(X * X, axis=-1)


def sq_dists(X, Y, precision: str):
    """‖x − y‖² for all pairs, (n, m), through the Gram expansion and
    clamped at 0 (the expansion can dip below 0 in floating point)."""
    d2 = sq_norms(X)[:, None] + sq_norms(Y)[None, :] - 2.0 * cross(
        X, Y, precision)
    return jnp.maximum(d2, 0.0)
