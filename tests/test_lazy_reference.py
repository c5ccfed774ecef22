"""The device CELF (``lazy_greedy(mode="device")``) against the benchmark's
plain CELF reference (``bench/reference/lazy.py``), through the readings
that decide the lazy selection cell's ``correct``, on the CPU at small
sizes (n = 1,024 and 2,048, d = 100, k = 10, Pallas kernels in interpret
mode); the cell itself as ``bench/run.py`` runs it, with its control and
each fault of ``bench/faults_lazy.py``; and the readings on selections
made wrong on purpose."""
import io
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import datagen, faults_lazy, run  # noqa: E402
from bench.reference import greedy as greedy_ref  # noqa: E402
from bench.reference import lazy as reference  # noqa: E402
from repro.core import (EvalConfig, ExemplarClustering,  # noqa: E402
                        lazy_greedy)

K, TOP_B = 10, 256
CELL = "paper_v_a_lazy.lazy_selection"
SMALL = dict(n=1024, d=100, k=K, backend="pallas_interpret")
FAULTS = faults_lazy.FAULTS["lazy_selection"]
with open(os.path.join(ROOT, "bench", "limits", f"{CELL}.json")) as f:
    LIMITS = json.load(f)["limits"]


def ground_set(n, seed, d=100):
    return datagen.uniform(datagen.seed_key(seed), n=n, d=d, low=0.0,
                           high=1.0)


def device_lazy(V, backend="pallas_interpret"):
    return lazy_greedy(ExemplarClustering(V, EvalConfig(backend=backend)), K,
                       batch=TOP_B, mode="device")


@pytest.mark.parametrize("seed", [3, 2**33 + 5])
@pytest.mark.parametrize("n", [1024, 2048])
def test_device_lazy_is_correct(n, seed):
    V = ground_set(n, seed)
    res = device_lazy(V)
    got = reference.readings(V, res.indices, res.trajectory, res.evaluations,
                             TOP_B)
    ok, checks = run.judge(got, LIMITS)
    assert ok, checks
    assert got["gain_gap_rel"] == 0


@pytest.mark.parametrize("n", [1024, 2048])
def test_device_trips_match_the_host_mirror_and_the_reference(n):
    V = ground_set(n, 9)
    dev = device_lazy(V)
    host = lazy_greedy(ExemplarClustering(V, EvalConfig()), K, batch=TOP_B,
                       mode="host")
    evals, trips = reference.count(V, dev.indices, TOP_B)
    assert dev.indices == host.indices
    assert dev.rescore_trips == host.rescore_trips == trips
    assert dev.evaluations == host.evaluations == evals
    # every round re-scores at least once; the seeding pass is no trip
    assert dev.rescore_trips >= K
    assert dev.evaluations <= n + dev.rescore_trips * TOP_B


def test_the_policy_certifies_greedys_pick():
    """After each round of the reference's policy the largest bound left
    is an exact gain, the round's largest: CELF picks Greedy's argmax."""
    V = ground_set(1024, 10)
    picks, _, _ = greedy_ref.greedy(V, K)
    cache = jnp.sum(V * V, axis=1)
    taken = np.zeros(1024, bool)
    bound = None
    for j in picks:
        g = greedy_ref.gains(V, cache).astype(np.float64)
        bound = g.copy() if bound is None else bound
        evals, trips = reference._round(bound, taken, g, TOP_B)
        live_bound = np.where(taken, -np.inf, bound)
        assert trips >= 1 and evals <= trips * TOP_B
        assert live_bound.max() == np.where(taken, -np.inf, g).max()
        assert int(np.argmax(live_bound)) == j
        taken[j] = True
        cache = greedy_ref._fold(V, cache, j, "highest")


def test_top_batch_breaks_ties_to_the_lower_index():
    stale = np.array([1.0, 3.0, 2.0, 3.0, -np.inf, 2.0, 2.0])
    assert sorted(reference._top(stale, 3)) == [1, 2, 3]
    assert sorted(reference._top(stale, 4)) == [1, 2, 3, 5]
    assert sorted(reference._top(stale, 10)) == [0, 1, 2, 3, 5, 6]


@pytest.mark.parametrize("trips", [-1, 1])
def test_an_evaluation_count_a_trip_off_is_refused(trips):
    V = ground_set(1024, 11)
    picks, values, evals, _ = reference.celf(V, K, TOP_B)
    got = reference.readings(V, picks, values, evals + trips * TOP_B, TOP_B)
    assert got["evals_err"] == TOP_B
    ok, checks = run.judge(got, LIMITS)
    assert not ok and checks["gain_gap_rel"]["value"] == 0


def test_a_pick_taken_twice_reads_inf():
    V = ground_set(1024, 12)
    picks, values, evals, _ = reference.celf(V, K, TOP_B)
    picks = list(picks)
    picks[4] = picks[0]
    got = reference.readings(V, picks, values, evals, TOP_B)
    assert got["gain_gap_rel"] == math.inf
    assert not run.judge(got, LIMITS)[0]


# -- the cell, as bench/run.py runs it ---------------------------------------


def run_small(seed, control=False):
    log = io.StringIO()
    result = run.run_cell(CELL, seed, 1.0, False, need_chip=False,
                          control=control, log=log, overrides=SMALL)
    return result, log.getvalue()


@pytest.fixture
def fresh_programs():
    """Programs traced before a fault was planted would hide it."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_the_cell_uses_the_lazy_faults():
    _, _, traffic = run.cell_parts(CELL)
    assert traffic["generator"] in faults_lazy.FAULTS


def test_cell_program_is_correct():
    result, log = run_small(2**31 + 11)
    assert result["correct"], log
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"evals_per_s", "setup_s"}


@pytest.mark.parametrize("seed", [5, 2**32 + 7])
def test_cell_control_is_refused(seed):
    result, log = run_small(seed, control=True)
    assert not result["correct"], log


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_cell_fault_is_refused(fault, fresh_programs):
    with FAULTS[fault]():
        result, log = run_small(21)
    assert not result["correct"], log
