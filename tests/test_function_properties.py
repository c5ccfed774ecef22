"""Submodularity property suite over the registered function zoo.

Every function in ``FUNCTIONS`` must be a monotone submodular set function
under the cache-semantics protocol — the greedy (1−1/e) guarantee, the lazy
upper-bound invariant, and the sieve threshold rules all assume exactly
monotonicity + diminishing returns, so a zoo entry that silently violates
either would corrupt every optimizer built on the protocol. The checks run
the protocol itself (``init_cache`` / ``gains_from_cache`` /
``fold_winner``/ ``value_from_cache``) at fp32 on hypothesis-drawn ground
sets:

* **monotonicity**: every candidate's marginal gain vs every prefix cache
  is ≥ 0 (up to fp32 reduction noise);
* **diminishing returns**: for a FIXED held-out candidate c, Δ(c | S_t) is
  non-increasing along a greedy chain S_0 ⊂ S_1 ⊂ … (the submodularity
  instance the cache update must preserve);
* **value consistency**: f(S) from ``value_from_cache`` equals f(∅) plus
  the telescoped sum of the accepted gains (the trajectory identity every
  engine's ``value_of`` relies on).

Graph cut is certified at λ = 0.5 — the monotonicity boundary its
constructor enforces; saturated coverage at its default cap fraction.
"""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="optional test extra; pip install .[test]")
from hypothesis import given, settings, strategies as st

from repro.core import EvalConfig
from repro.core.functions import FUNCTIONS

#: fp32 mean-reductions over ≤ 48 rows: gains are exact to ~1e-6 of the
#: O(1) similarity scale; the slack absorbs non-associative sum noise.
TOL = 1e-5

ZOO = sorted(FUNCTIONS)


def _make_function(name: str, n: int, d: int, seed: int):
    rng = np.random.default_rng(seed)
    V = jnp.asarray((rng.normal(size=(n, d)) * 0.4).astype(np.float32))
    # rbf keeps the similarity dense so the coverage-style objectives see a
    # non-degenerate problem (raw sqeuclidean at unit scale saturates
    # s = relu(1 − d/2) to 0 and every property holds vacuously)
    return FUNCTIONS[name](V, EvalConfig(distance="rbf"))


@pytest.mark.parametrize("name", ZOO)
@settings(max_examples=20, deadline=None)
@given(n=st.integers(6, 48), d=st.integers(2, 8),
       seed=st.integers(0, 2**31 - 1), data=st.data())
def test_monotone_and_diminishing_returns(name, n, d, seed, data):
    f = _make_function(name, n, d, seed)
    k = min(5, n - 1)
    held_out = data.draw(st.integers(0, n - 1), label="held-out candidate")
    order = data.draw(st.permutations(range(n)), label="greedy tie order")

    cache = f.init_cache()
    all_idx = jnp.arange(n, dtype=jnp.int32)
    # graph cut alone is checked, and chained, outside S_t: its additive
    # cache formula scores a member j as if j were added a second time, not
    # its true gain 0 — negative (n=6, d=5, seed=0: −0.028) or positive, so
    # an unmasked argmax may fold j twice. Every engine masks members before
    # its argmax, so no selection reads that score (ROADMAP Design debt 6).
    taken = np.zeros(n, bool)   # stays all-False for every other function
    held_gains = []
    for _ in range(k):
        gains = np.asarray(f.gains_from_cache(cache, all_idx), np.float64)
        # monotonicity: every marginal gain of every candidate vs S_t
        assert gains[~taken].min() >= -TOL, (name, gains[~taken].min())
        held_gains.append(gains[held_out])
        # fold a (drawn-order) near-argmax winner to advance the chain; the
        # drawn order only breaks exact ties, so this stays a greedy chain
        j = max((i for i in order if not taken[i]), key=lambda i: gains[i])
        taken[j] = name == "graph_cut"
        cache = f.fold_winner(cache, jnp.int32(j))
    # diminishing returns for the fixed candidate along the chain
    for a, b in zip(held_gains, held_gains[1:]):
        assert b <= a + TOL, (name, held_gains)


@pytest.mark.parametrize("name", ZOO)
@settings(max_examples=20, deadline=None)
@given(n=st.integers(6, 48), d=st.integers(2, 8),
       seed=st.integers(0, 2**31 - 1))
def test_value_telescopes_from_gains(name, n, d, seed):
    f = _make_function(name, n, d, seed)
    k = min(5, n - 1)
    cache = f.init_cache()
    total = float(np.asarray(f.value_from_cache(cache)))  # f(∅), 0 for all
    assert abs(total) <= TOL
    for t in range(k):
        j = (seed + 7 * t) % n  # arbitrary (not greedy) chain — must still hold
        total += float(np.asarray(
            f.gains_from_cache(cache, jnp.asarray([j], jnp.int32)))[0])
        cache = f.fold_winner(cache, jnp.int32(j))
    np.testing.assert_allclose(float(np.asarray(f.value_from_cache(cache))),
                               total, atol=5e-5)
