"""Greedy (arXiv:2101.08763, Algorithm 1) on exemplar clustering, plainly.

    L(S) = n⁻¹ Σ_i min_{s ∈ S ∪ {e0}} ‖v_i − s‖²,   f(S) = L({e0}) − L(S)

with e0 the all-zero vector. Round r adds to S_r = {j_0, …, j_{r−1}} the
candidate v ∉ S_r of largest f(S_r ∪ {v}), which is the one of largest gain

    f(S_r ∪ {v}) − f(S_r) = n⁻¹ Σ_i max(m_i − ‖v_i − v‖², 0),
    m_i = min_{s ∈ S_r ∪ {e0}} ‖v_i − s‖²,

since min(m, x) = m − max(m − x, 0). Each round scores every one of the
n − r live candidates, a block of candidates at a time so that the (n,
block) distance matrix fits beside whatever else the device holds. No
kernel, no scan, nothing of the program.

:func:`greedy` is the algorithm itself; :func:`replay` follows picks made
elsewhere and scores every round as the algorithm would; :func:`readings`
compares a selection with its replay.
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.arith import sq_dists, sq_norms

#: distances a block holds at most: 500 MB of float32
BLOCK_DISTANCES = 125_000_000


@partial(jax.jit, static_argnames=("block", "precision"))
def _block_gains(V, cache, lo, block: int, precision: str):
    """Gains of the candidates V[lo : lo + block] under ``cache``."""
    C = jax.lax.dynamic_slice_in_dim(V, lo, block)
    D = sq_dists(V, C, precision)
    return jnp.mean(jnp.maximum(cache[:, None] - D, 0.0), axis=0)


@partial(jax.jit, static_argnames=("precision",))
def _fold(V, cache, j, precision: str):
    """The cache once V[j] has joined the set."""
    return jnp.minimum(cache, sq_dists(V, V[j][None, :], precision)[:, 0])


def gains(V, cache, precision: str = "highest",
          block: int | None = None) -> np.ndarray:
    """(n,) float32 gains of every point of V as the next pick under the
    cache ``cache`` (m_i above), ``block`` candidates at a time (by default
    as many as keep the block's distances under ``BLOCK_DISTANCES``)."""
    n = V.shape[0]
    block = min(n, block or max(1, BLOCK_DISTANCES // n))
    out = []
    for lo in range(0, n, block):
        start = min(lo, n - block)   # the last block ends at n
        out.append(np.asarray(_block_gains(V, cache, start, block,
                                           precision))[lo - start:])
    return np.concatenate(out)


class Rounds(NamedTuple):
    """Per round r of a selection: the pick j_r, the largest gain g*_r over
    the live candidates, the gain g_r(j_r) of the pick (NaN for a pick out
    of range or already taken, after which nothing is scored) and
    f(S_{r+1})."""

    picks: list[int]
    best: list[float]
    chosen: list[float]
    values: list[float]


def replay(V, k: int, precision: str = "highest", picks=None) -> Rounds:
    """k rounds of Algorithm 1 on V. With ``picks``, round r takes
    ``picks[r]`` in place of its own argmax and is scored all the same."""
    n = V.shape[0]
    cache = sq_norms(V)
    L0 = jnp.mean(cache)
    taken = np.zeros(n, bool)
    out = Rounds([], [], [], [])
    for r in range(k):
        g = gains(V, cache, precision).astype(np.float64)
        g[taken] = -np.inf
        j = int(np.argmax(g)) if picks is None else int(picks[r])
        out.picks.append(j)
        out.best.append(float(g.max()))
        if not 0 <= j < n or taken[j]:
            out.chosen.append(math.nan)
            break
        out.chosen.append(float(g[j]))
        taken[j] = True
        cache = _fold(V, cache, j, precision)
        out.values.append(float(L0 - jnp.mean(cache)))
    return out


def greedy(V, k: int, precision: str = "highest"):
    """Algorithm 1: the picks, f(S_1), …, f(S_k), and the number of
    candidates scored, Σ_{r<k} (n − r)."""
    rounds = replay(V, k, precision)
    return rounds.picks, rounds.values, scored(V.shape[0], k)


def scored(n: int, k: int) -> int:
    """Candidates Algorithm 1 scores in k rounds over n points: every live
    one, every round."""
    return sum(n - r for r in range(k))


def readings(V, picks, trajectory, evaluations) -> dict[str, float]:
    """A selection of k = len(picks) against its replay at HIGHEST.

    - ``gain_gap_rel``: the largest (g*_r − g_r(j_r)) ÷ g*_r over the
      rounds: 0 where each pick is the replay's argmax, small at a
      near-tie, +inf for a pick out of range or taken before;
    - ``value_rel_err``: the largest |trajectory_r − f(S_{r+1})| ÷
      f(S_{r+1}), +inf where the trajectory has another length;
    - ``evals_err``: |evaluations − Σ_{r<k} (n − r)|.
    """
    k = len(picks)
    rounds = replay(V, k, "highest", picks=picks)
    gap = 0.0
    for best, chosen in zip(rounds.best, rounds.chosen):
        if math.isnan(chosen):
            gap = math.inf
        elif best > 0:
            gap = max(gap, (best - chosen) / best)
    traj = [float(t) for t in trajectory]
    if len(traj) != k or len(rounds.values) != k or \
            not all(map(math.isfinite, traj)):
        err = math.inf
    else:
        err = max((abs(t - v) / abs(v) for t, v in zip(traj, rounds.values)),
                  default=0.0)
    return {"gain_gap_rel": gap, "value_rel_err": err,
            "evals_err": float(abs(int(evaluations) - scored(V.shape[0], k)))}
