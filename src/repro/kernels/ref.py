"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth for the kernel test sweeps (shapes × dtypes,
``assert_allclose``). They intentionally mirror the *mathematical* definition,
not the kernel's tiling, so a tiling bug cannot cancel out.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.precision import FP32, PrecisionPolicy, matmul_precision


def _pair_sqdist(V, S, policy: PrecisionPolicy):
    Vc = V.astype(policy.compute_dtype)
    Sc = S.astype(policy.compute_dtype)
    g = jax.lax.dot_general(
        Vc, Sc, (((1,), (1,)), ((), ())),
        precision=matmul_precision(Vc.dtype),
        preferred_element_type=policy.accum_dtype,
    )
    vn = jnp.sum(Vc.astype(policy.accum_dtype) ** 2, -1)
    sn = jnp.sum(Sc.astype(policy.accum_dtype) ** 2, -1)
    return jnp.maximum(vn[:, None] + sn[None, :] - 2.0 * g, 0.0)


def exemplar_eval_ref(
    V: jax.Array,
    S: jax.Array,           # (l, k, d)
    lengths: jax.Array,     # (l,)
    d_e0: jax.Array,        # (n,) final (possibly transformed) dist to e0
    policy: PrecisionPolicy = FP32,
    rbf_gamma: Optional[float] = None,
) -> jax.Array:
    """L(S_j ∪ {e0}) for all j — (l,) float32."""
    n = V.shape[0]
    l, k, d = S.shape
    D = _pair_sqdist(V, S.reshape(l * k, d), policy).reshape(n, l, k)
    if rbf_gamma is not None:
        D = 2.0 * (1.0 - jnp.exp(-rbf_gamma * D))
    mask = jnp.arange(k)[None, :] < lengths[:, None]
    big = jnp.asarray(jnp.finfo(D.dtype).max, D.dtype)
    D = jnp.where(mask[None, :, :], D, big)
    dmin = jnp.minimum(jnp.min(D, axis=-1), d_e0[:, None].astype(D.dtype))
    return (jnp.sum(dmin.astype(jnp.float32), axis=0) / n).astype(jnp.float32)


def work_matrix_ref(
    V: jax.Array, S: jax.Array, lengths: jax.Array, d_e0: jax.Array,
    policy: PrecisionPolicy = FP32, rbf_gamma: Optional[float] = None,
) -> jax.Array:
    """The paper's W — (l, n): min-dist(v_i, S_j ∪ {e0}) / n."""
    n = V.shape[0]
    l, k, d = S.shape
    D = _pair_sqdist(V, S.reshape(l * k, d), policy).reshape(n, l, k)
    if rbf_gamma is not None:
        D = 2.0 * (1.0 - jnp.exp(-rbf_gamma * D))
    mask = jnp.arange(k)[None, :] < lengths[:, None]
    big = jnp.asarray(jnp.finfo(D.dtype).max, D.dtype)
    D = jnp.where(mask[None, :, :], D, big)
    dmin = jnp.minimum(jnp.min(D, axis=-1), d_e0[:, None].astype(D.dtype))
    return (dmin.T / n).astype(jnp.float32)


def marginal_gain_ref(
    V: jax.Array,
    C: jax.Array,           # (m, d) candidates
    mincache: jax.Array,    # (n,)
    policy: PrecisionPolicy = FP32,
    rbf_gamma: Optional[float] = None,
) -> jax.Array:
    """Δ(c_j | S) = |V|⁻¹ Σ_i relu(m_i − d(v_i, c_j)) — (m,) float32."""
    D = _pair_sqdist(V, C, policy)
    if rbf_gamma is not None:
        D = 2.0 * (1.0 - jnp.exp(-rbf_gamma * D))
    g = jnp.maximum(mincache[:, None].astype(D.dtype) - D, 0.0)
    return (jnp.sum(g.astype(jnp.float32), axis=0) / V.shape[0]).astype(jnp.float32)
