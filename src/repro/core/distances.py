"""Dissimilarity functions for exemplar-based clustering.

Exemplar clustering only requires non-negativity of ``d`` (paper §IV), not the
triangle inequality. All functions here are exposed in two forms:

* ``pairwise(X, Y) -> (n, m)`` — the full cross matrix, used by the work-matrix
  evaluator. For inner-product-expressible distances (squared Euclidean,
  cosine, RBF) this routes the heavy term through a single matmul so the TPU
  MXU does the work (see DESIGN.md §2).
* ``point(x, y) -> scalar`` — the direct definition, used by oracles/tests.

Gram-based distances clamp at zero: the expansion ``‖x‖²+‖y‖²−2⟨x,y⟩`` can go
slightly negative in floating point.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.precision import FP32, PrecisionPolicy, matmul_precision


def _dot(X: jax.Array, Y: jax.Array, accum_dtype) -> jax.Array:
    """(n,d)·(m,d)ᵀ with explicit accumulation dtype (MXU-friendly)."""
    return jax.lax.dot_general(
        X,
        Y,
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=matmul_precision(X.dtype),
        preferred_element_type=accum_dtype,
    )


def sq_norms(X: jax.Array, accum_dtype=jnp.float32) -> jax.Array:
    """Row-wise ‖x‖² with explicit accumulation dtype.

    A half-precision payload must NOT be up-cast wholesale (that
    materializes a payload-sized fp32 copy — the exact traffic the
    compute/accum split avoids); the self-inner-product routes through
    ``dot_general`` so the widening rides ``preferred_element_type``
    inside the unit, like the Gram matmul's. (Audit fixture:
    ``precision.sq-norms-upcast`` in tests/test_analysis.py.)
    """
    if X.dtype == jnp.dtype(accum_dtype):
        return jnp.sum(X * X, axis=-1)
    contract = (X.ndim - 1,)
    batch = tuple(range(X.ndim - 1))
    return jax.lax.dot_general(
        X, X,
        dimension_numbers=((contract, contract), (batch, batch)),
        precision=matmul_precision(X.dtype),
        preferred_element_type=accum_dtype,
    )


def sqeuclidean_pairwise(
    X: jax.Array, Y: jax.Array, policy: PrecisionPolicy = FP32
) -> jax.Array:
    """‖x−y‖² for all pairs via the Gram expansion (one MXU matmul)."""
    Xc = X.astype(policy.compute_dtype)
    Yc = Y.astype(policy.compute_dtype)
    g = _dot(Xc, Yc, policy.accum_dtype)
    xn = sq_norms(Xc, policy.accum_dtype)
    yn = sq_norms(Yc, policy.accum_dtype)
    d2 = xn[:, None] + yn[None, :] - 2.0 * g
    return jnp.maximum(d2, 0.0)


def sqeuclidean_point(x: jax.Array, y: jax.Array) -> jax.Array:
    diff = x.astype(jnp.float32) - y.astype(jnp.float32)
    return jnp.sum(diff * diff)


def manhattan_pairwise(
    X: jax.Array, Y: jax.Array, policy: PrecisionPolicy = FP32
) -> jax.Array:
    """Σ|x−y| — not inner-product-expressible; direct broadcast (VPU path)."""
    Xc = X.astype(policy.accum_dtype)
    Yc = Y.astype(policy.accum_dtype)
    return jnp.sum(jnp.abs(Xc[:, None, :] - Yc[None, :, :]), axis=-1)


def manhattan_point(x: jax.Array, y: jax.Array) -> jax.Array:
    return jnp.sum(jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32)))


def cosine_pairwise(
    X: jax.Array, Y: jax.Array, policy: PrecisionPolicy = FP32
) -> jax.Array:
    """1 − cos(x, y) ∈ [0, 2]; Gram-based. Zero vectors map to dissimilarity 1."""
    Xc = X.astype(policy.compute_dtype)
    Yc = Y.astype(policy.compute_dtype)
    g = _dot(Xc, Yc, policy.accum_dtype)
    xn = jnp.sqrt(sq_norms(Xc, policy.accum_dtype))
    yn = jnp.sqrt(sq_norms(Yc, policy.accum_dtype))
    denom = jnp.maximum(xn[:, None] * yn[None, :], 1e-30)
    return jnp.maximum(1.0 - g / denom, 0.0)


def cosine_point(x: jax.Array, y: jax.Array) -> jax.Array:
    x = x.astype(jnp.float32)
    y = y.astype(jnp.float32)
    denom = jnp.maximum(jnp.linalg.norm(x) * jnp.linalg.norm(y), 1e-30)
    return jnp.maximum(1.0 - jnp.dot(x, y) / denom, 0.0)


#: Default RBF bandwidth — the single source of truth shared by the jnp
#: pairwise form and the Pallas kernel paths (host/device parity depends on
#: both sides using the same gamma).
RBF_GAMMA = 1.0


def rbf_pairwise(
    X: jax.Array, Y: jax.Array, policy: PrecisionPolicy = FP32,
    gamma: float = RBF_GAMMA,
) -> jax.Array:
    """Kernel-induced dissimilarity d(x,y) = 2·(1 − exp(−γ‖x−y‖²)) ≥ 0.

    The paper notes dissimilarities may be constructed from Mercer kernels;
    this is the RBF instance: d = k(x,x) + k(y,y) − 2k(x,y) with k RBF.
    """
    d2 = sqeuclidean_pairwise(X, Y, policy)
    return 2.0 * (1.0 - jnp.exp(-gamma * d2))


def rbf_point(x: jax.Array, y: jax.Array, gamma: float = RBF_GAMMA) -> jax.Array:
    return 2.0 * (1.0 - jnp.exp(-gamma * sqeuclidean_point(x, y)))


PAIRWISE: dict[str, Callable] = {
    "sqeuclidean": sqeuclidean_pairwise,
    "manhattan": manhattan_pairwise,
    "cosine": cosine_pairwise,
    "rbf": rbf_pairwise,
}

POINT: dict[str, Callable] = {
    "sqeuclidean": sqeuclidean_point,
    "manhattan": manhattan_point,
    "cosine": cosine_point,
    "rbf": rbf_point,
}

#: Distances whose pairwise form routes the dominant term through a matmul and
#: therefore through the fused Pallas kernels (kernels assume sqeuclidean).
MXU_ELIGIBLE = frozenset({"sqeuclidean", "rbf"})


def resolve_pairwise(name: str) -> Callable:
    try:
        return PAIRWISE[name]
    except KeyError as e:
        raise ValueError(f"unknown distance {name!r}; options {sorted(PAIRWISE)}") from e
