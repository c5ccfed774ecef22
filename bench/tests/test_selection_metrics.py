"""The selection cell's work counts and per-layer readers, on hand counts
and on a recorded trace.

``data/selection_3calls.xplane.pb`` was recorded on one TPU v5 lite: three
``greedy(mode="device")`` selections (n=4,096, d=100, k=10, Pallas fp32),
each building its ``ExemplarClustering`` inside a ``select`` span, 5 ms
apart, inside one ``window`` span, from a program that opens the
selection spans of ``src/repro/core/tracing.py``. ``data/
multiset_3calls.xplane.pb`` is a trace with none of them."""
import math
import os

import pytest

from bench import run, trace, work_selection
from bench.generators import base
from bench.generators.selection import Generator

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "selection_3calls.xplane.pb")
NO_SPANS = os.path.join(DATA, "multiset_3calls.xplane.pb")
READERS = ("roofline.selection", "device.idle_share.selection",
           "engine.prepare_ms.selection", "function.init_ms.selection")
N, D, K, CALLS = 4096, 100, 10, 3


def test_dense_greedy_counts():
    # n=3, d=2, k=2: rounds score 3 and 2 live candidates, 2·3·2 FLOPs
    # each; one winner folded, 2·3·2; each round reads V (3·2 floats) and
    # moves 3·3 cache and gain floats
    assert work_selection.dense_greedy(3, 2, 2) == (
        12 * 3 + 12 * 2 + 12, 2 * (6 * 4 + 9 * 4))
    assert work_selection.dense_greedy(3, 2, 2, itemsize=2)[1] == \
        2 * (6 * 2 + 9 * 4)


def test_counts_at_the_cell_size():
    # Σ_{r<10} (50,000 − r) = 499,955 live candidates of 2·50,000·100 FLOPs
    flops, nbytes = work_selection.dense_greedy(50_000, 100, 10)
    assert flops == 499_955 * 10**7 + 9 * 10**7
    assert nbytes == 10 * (2 * 10**7 + 600_000)


def context(path):
    reduced = trace.reduce(path, span_names=Generator.span_names)
    window = base.Window(
        seconds=reduced.window_s, attempted=CALLS, failed=0,
        end_to_end={}, work={"dense_greedy": dict(
            n=N, d=D, k=K, itemsize=4, calls=CALLS,
            scored=[sum(N - r for r in range(K))] * CALLS)})
    peak = run.load_json(run.BENCH, "peaks.json")["TPU v5 lite"]
    return base.MetricContext(reduced=reduced, peak=peak, window=window)


@pytest.fixture(scope="module")
def recorded():
    return context(RECORDED)


def test_recorded_spans_open_once_per_selection(recorded):
    counts = {name: c for name, (c, _) in recorded.reduced.spans.items()}
    assert counts == dict.fromkeys(Generator.span_names, CALLS)
    secs = {name: s for name, (_, s) in recorded.reduced.spans.items()}
    assert secs["run_selection.prepare"] + secs["run_selection.fetch"] \
        <= secs["run_selection"]
    assert secs["run_selection"] + secs["function.init"] <= secs["select"]


def test_recorded_gain_kernel_is_the_top_op(recorded):
    name, _ = recorded.reduced.top_ops[0]
    assert "gain_update_eval" in name


@pytest.mark.parametrize("name", READERS)
def test_readers_are_finite_on_the_recorded_trace(recorded, name):
    value = run.load_metric(name).read(recorded)
    assert value is not None and math.isfinite(value) and value > 0


def test_roofline_is_a_share(recorded):
    assert run.load_metric("roofline.selection").read(recorded) < 1


@pytest.mark.parametrize("name", ["engine.prepare_ms.selection",
                                  "function.init_ms.selection"])
def test_span_readers_are_none_where_the_span_is_missing(name):
    assert run.load_metric(name).read(context(NO_SPANS)) is None


def test_roofline_is_none_without_selection_work(recorded):
    ctx = base.MetricContext(reduced=recorded.reduced, peak=recorded.peak,
                             window=base.Window(1.0, 1, 0, {}, {}))
    assert run.load_metric("roofline.selection").read(ctx) is None


def test_every_reader_is_listed_for_the_cell():
    spec = run.load_json(run.ROOT, "BENCHMARK.json")
    _, layer = run.cell_metrics(spec, "paper_v_a_greedy.selection")
    assert sorted(m["name"] for m in layer) == sorted(READERS)
