"""The device Greedy against the benchmark's plain greedy reference
(``bench/reference/greedy.py``), through the readings that decide the
selection cell's ``correct``, on the CPU at small sizes; and the readings
themselves, on selections made wrong on purpose."""
import json
import math
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import datagen, run  # noqa: E402
from bench.reference import greedy as reference  # noqa: E402
from repro.core import EvalConfig, ExemplarClustering, greedy  # noqa: E402

K = 10
with open(os.path.join(ROOT, "bench", "limits",
                       "paper_v_a_greedy.selection.json")) as f:
    LIMITS = json.load(f)["limits"]


def ground_set(n, seed, d=100):
    return datagen.uniform(datagen.seed_key(seed), n=n, d=d, low=0.0,
                           high=1.0)


def device_greedy(V):
    res = greedy(ExemplarClustering(V, EvalConfig(backend="pallas_interpret")),
                 K, mode="device")
    return res.indices, res.trajectory, res.evaluations


@pytest.mark.parametrize("seed", [3, 4, 2**33 + 5])
@pytest.mark.parametrize("n", [512, 1024])
def test_device_greedy_is_correct(n, seed):
    V = ground_set(n, seed)
    picks, traj, evals = device_greedy(V)
    got = reference.readings(V, picks, traj, evals)
    ok, checks = run.judge(got, LIMITS)
    assert ok, checks
    assert evals == reference.scored(n, K)


def test_a_wrong_pick_opens_a_gain_gap():
    V = ground_set(512, 3)
    rounds = reference.replay(V, K)
    assert reference.readings(V, rounds.picks, rounds.values,
                              reference.scored(512, K))["gain_gap_rel"] == 0
    # round 2 takes the weakest live candidate in place of the best
    g = reference.gains(V, jnp.sum(V * V, axis=1)).astype(np.float64)
    g[rounds.picks] = np.inf
    picks = list(rounds.picks)
    picks[2] = int(np.argmin(g))
    got = reference.readings(V, picks, rounds.values, reference.scored(512, K))
    assert got["gain_gap_rel"] > LIMITS["gain_gap_rel"]["limit"]
    assert not run.judge(got, LIMITS)[0]


@pytest.mark.parametrize("bad", [-1, 512, "repeat"])
def test_a_pick_out_of_range_or_repeated_reads_inf(bad):
    V = ground_set(512, 4)
    rounds = reference.replay(V, K)
    picks = list(rounds.picks)
    picks[5] = picks[1] if bad == "repeat" else bad
    got = reference.readings(V, picks, rounds.values, reference.scored(512, K))
    assert got["gain_gap_rel"] == math.inf
    assert got["value_rel_err"] == math.inf
    assert not run.judge(got, LIMITS)[0]


@pytest.mark.parametrize("off", [-1, 1])
def test_an_evaluation_count_off_by_one_is_refused(off):
    V = ground_set(512, 5)
    rounds = reference.replay(V, K)
    got = reference.readings(V, rounds.picks, rounds.values,
                             reference.scored(512, K) + off)
    assert got["evals_err"] == 1
    ok, checks = run.judge(got, LIMITS)
    assert not ok and checks["gain_gap_rel"]["value"] == 0


def test_a_trajectory_of_another_length_reads_inf():
    V = ground_set(512, 6)
    rounds = reference.replay(V, K)
    got = reference.readings(V, rounds.picks, rounds.values[:-1],
                             reference.scored(512, K))
    assert got["value_rel_err"] == math.inf


def test_blocks_by_size_give_the_same_gains():
    V = ground_set(700, 7)
    cache = jnp.sum(V * V, axis=1)
    whole = reference.gains(V, cache)
    # 300 does not divide 700: the last block starts early and overlaps
    np.testing.assert_allclose(reference.gains(V, cache, block=300), whole,
                               rtol=1e-6)


def test_high_precision_control_is_refused():
    V = ground_set(512, 8)
    picks, traj, evals = reference.greedy(V, K, precision="high")
    got = reference.readings(V, picks, traj, evals)
    assert got["value_rel_err"] > LIMITS["value_rel_err"]["limit"]
    assert not run.judge(got, LIMITS)[0]
