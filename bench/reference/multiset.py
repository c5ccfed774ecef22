"""L(S_j ∪ {e0}) for a multiset of index sets over one ground set.

    L(S) = n⁻¹ Σ_i min_{s ∈ S} ‖v_i − s‖²        (arXiv:2101.08763, eq. 7)

with e0 the all-zero vector, so ‖v_i − e0‖² = ‖v_i‖². The sets are given
as indices into V and evaluated a block of sets at a time, so that the
(n, block·k) distance matrix fits beside whatever else the device holds.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.arith import sq_dists, sq_norms


@partial(jax.jit, static_argnames=("precision",))
def _block_values(V, idx, lengths, precision):
    b, k = idx.shape
    D = sq_dists(V, V[idx.reshape(-1)], precision).reshape(V.shape[0], b, k)
    live = jnp.arange(k)[None, None, :] < lengths[None, :, None]
    nearest = jnp.min(jnp.where(live, D, jnp.inf), axis=-1)
    nearest = jnp.minimum(nearest, sq_norms(V)[:, None])
    return jnp.mean(nearest, axis=0)


#: distances a block holds at most: 500 MB of float32
BLOCK_DISTANCES = 125_000_000


def multiset_values(V, idx, lengths, precision: str = "highest",
                    block_sets: int | None = None) -> np.ndarray:
    """(l,) float32 values of the l sets ``idx`` (l, k) of which the first
    ``lengths[j]`` indices count, ``block_sets`` sets at a time (by default
    as many as keep the block's distances under ``BLOCK_DISTANCES``)."""
    idx = jnp.asarray(idx, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    if block_sets is None:
        block_sets = max(1, BLOCK_DISTANCES // (V.shape[0] * idx.shape[1]))
    out = []
    for lo in range(0, idx.shape[0], block_sets):
        out.append(_block_values(V, idx[lo:lo + block_sets],
                                 lengths[lo:lo + block_sets], precision))
    return np.asarray(jnp.concatenate(out))
