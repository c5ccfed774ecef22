"""Lazy greedy (Minoux 1978; CELF, Leskovec et al. 2007, §3.1) on exemplar
clustering, plainly.

CELF picks what Greedy (``bench/reference/greedy.py``) picks: by
submodularity a candidate's gain can only shrink as the set grows, so a
gain scored in an earlier round bounds it from above in every later one,
and a re-scored gain at least as large as every bound left is the round's
largest. The policy here is the one the configuration states: the bounds
are seeded by every candidate's singleton gain (round −1, n scores); each
round re-scores the ``top_b`` live candidates of largest stale bound, a
batch (a trip) at a time, ties to the lower index, and stops once the best
re-scored gain is at least every bound not re-scored this round.

:func:`count` scores every live candidate of every round exactly, as
Greedy does, and plays the policy out in NumPy on those gains: how many
trips and evaluations it takes. Nothing of the program is used.
"""
from __future__ import annotations

import numpy as np

from bench.reference import greedy
from bench.reference.arith import sq_norms


def _top(stale: np.ndarray, top_b: int) -> np.ndarray:
    """The ``top_b`` finite entries of largest ``stale``, ties to the lower
    index (as a stable sort of −stale would order them)."""
    finite = np.flatnonzero(stale > -np.inf)
    if finite.size <= top_b:
        return finite
    cut = np.partition(stale[finite], finite.size - top_b)[finite.size - top_b]
    above = finite[stale[finite] > cut]
    at = finite[stale[finite] == cut][:top_b - above.size]
    return np.concatenate([above, at])


def _round(bound: np.ndarray, taken: np.ndarray, g: np.ndarray,
           top_b: int) -> tuple[int, int]:
    """One CELF round on the exact gains ``g``: updates ``bound`` in place
    and returns its (evaluations, trips)."""
    fresh = np.zeros(bound.shape, bool)
    evals = trips = 0
    while True:
        stale = np.where(fresh | taken, -np.inf, bound)
        fresh_best = np.max(np.where(fresh & ~taken, bound, -np.inf))
        if fresh_best >= stale.max():
            return evals, trips
        top = _top(stale, top_b)
        bound[top] = g[top]
        fresh[top] = True
        evals += top.size
        trips += 1


def count(V, picks, top_b: int, precision: str = "highest"
          ) -> tuple[int, int]:
    """CELF's evaluations and trips over the rounds that take ``picks``,
    on gains scored exactly at ``precision``; a pick out of range or taken
    before ends the count."""
    n = V.shape[0]
    cache = sq_norms(V)
    taken = np.zeros(n, bool)
    bound = None
    evals = trips = 0
    for j in picks:
        g = greedy.gains(V, cache, precision).astype(np.float64)
        if bound is None:              # round −1: every singleton gain
            bound, evals = g.copy(), n
        e, t = _round(bound, taken, g, top_b)
        evals, trips = evals + e, trips + t
        if not 0 <= j < n or taken[j]:
            break
        taken[j] = True
        cache = greedy._fold(V, cache, j, precision)
    return evals, trips


def celf(V, k: int, top_b: int, precision: str = "highest"):
    """CELF: Greedy's picks and f(S_1), …, f(S_k), with the evaluations
    and trips CELF takes to make them."""
    picks, values, _ = greedy.greedy(V, k, precision)
    return (picks, values, *count(V, picks, top_b, precision))


def readings(V, picks, trajectory, evaluations, top_b: int
             ) -> dict[str, float]:
    """A selection of k = len(picks) against the reference at HIGHEST:
    ``gain_gap_rel`` and ``value_rel_err`` as Greedy's
    (:func:`greedy.readings`: CELF must pick Greedy's argmax), and
    ``evals_err``, |evaluations − CELF's evaluations following these
    picks|."""
    got = greedy.readings(V, picks, trajectory, evaluations)
    evals, _ = count(V, picks, top_b)
    return dict(got, evals_err=float(abs(int(evaluations) - evals)))
