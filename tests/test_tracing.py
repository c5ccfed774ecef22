"""The program's host spans (``repro.core.tracing``), read back from a
profiler trace of ``evaluate_multiset`` and of device ``greedy`` on the
CPU.

The spans land in the trace's ``/host:CPU`` plane under their names, on
the same clock as the device's operations; the benchmark's per-layer
metrics read them there, so each must open once per call and the child
spans must lie inside ``evaluate_multiset`` or ``run_selection``."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (EvalConfig, ExemplarClustering, evaluate_multiset,
                        greedy, lazy_greedy, pack_sets, stochastic_greedy,
                        tracing)
from repro.core.engine import run_selection_batch
from repro.core.evaluator import e0_distances

CALLS = 2


def _problem(n=64, l=8, k=3, d=8, seed=0):
    rng = np.random.default_rng(seed)
    V = jnp.asarray(rng.random((n, d), np.float32))
    sets = [V[rng.choice(n, size=rng.integers(1, k + 1), replace=False)]
            for _ in range(l)]
    return V, pack_sets(sets)


def _spans(log_dir) -> list[tuple[int, int, str]]:
    """(start ns, end ns, name) of every program span in the trace."""
    from jax.profiler import ProfileData

    found = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(found) == 1, found
    pd = ProfileData.from_file(found[0])
    out = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                       for e in line.events if e.name in tracing.SPANS)
    return sorted(out)


def _traced_calls(tmp_path, V, packed, cfg, **kw):
    evaluate_multiset(V, packed, cfg, **kw).block_until_ready()  # compile
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(CALLS):
            evaluate_multiset(V, packed, cfg, **kw).block_until_ready()
    return _spans(tmp_path)


@pytest.mark.parametrize("backend", ["jnp", "pallas_interpret"])
def test_each_span_opens_once_per_call(tmp_path, backend):
    V, packed = _problem()
    spans = _traced_calls(tmp_path, V, packed, EvalConfig(backend=backend))
    counts = {name: sum(1 for *_, n in spans if n == name)
              for name in tracing.SPANS}
    # only the kernel backends go through kernels.ops.exemplar_eval
    kernel = CALLS if backend == "pallas_interpret" else 0
    # no selection span opens inside the evaluator
    assert counts == dict.fromkeys(tracing.SPANS, 0) | {
        tracing.EVALUATE_MULTISET: CALLS, tracing.E0_DISTANCES: CALLS,
        tracing.EXEMPLAR_EVAL: kernel}
    outer = [(s, e) for s, e, n in spans if n == tracing.EVALUATE_MULTISET]
    for s, e, name in spans:
        if name == tracing.EVALUATE_MULTISET:
            continue
        inside = [o for o in outer if o[0] <= s and e <= o[1]]
        assert len(inside) == 1, (name, s, e, outer)


@pytest.mark.parametrize("backend", ["jnp", "pallas_interpret"])
def test_no_e0_span_when_the_caller_passes_d_e0(tmp_path, backend):
    V, packed = _problem(seed=1)
    cfg = EvalConfig(backend=backend)
    d_e0 = e0_distances(V, None, cfg.distance, cfg.policy)
    spans = _traced_calls(tmp_path, V, packed, cfg, d_e0=d_e0)
    names = [n for *_, n in spans]
    assert tracing.E0_DISTANCES not in names
    assert names.count(tracing.EVALUATE_MULTISET) == CALLS


def _traced_selections(tmp_path, V, cfg, k=3, select=greedy):
    select(ExemplarClustering(V, cfg), k, mode="device")  # compile
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(CALLS):
            select(ExemplarClustering(V, cfg), k, mode="device")
    return _spans(tmp_path)


@pytest.mark.parametrize("backend", ["jnp", "pallas_interpret"])
def test_selection_spans_open_once_per_call(tmp_path, backend):
    V, _ = _problem(seed=2)
    _check_selection_spans(
        _traced_selections(tmp_path, V, EvalConfig(backend=backend)))


@pytest.mark.parametrize("backend", ["jnp", "pallas_interpret"])
def test_lazy_selection_spans_open_once_per_call(tmp_path, backend):
    """CELF's counter rides the result, so its selection opens the same
    host spans as dense Greedy's."""
    V, _ = _problem(seed=2)
    _check_selection_spans(_traced_selections(
        tmp_path, V, EvalConfig(backend=backend), select=lazy_greedy))


def _check_selection_spans(spans):
    counts = {name: sum(1 for *_, n in spans if n == name)
              for name in tracing.SPANS}
    assert counts == dict.fromkeys(tracing.SPANS, 0) | {
        tracing.RUN_SELECTION: CALLS, tracing.RUN_SELECTION_PREPARE: CALLS,
        tracing.RUN_SELECTION_FETCH: CALLS, tracing.FUNCTION_INIT: CALLS}
    outer = [(s, e) for s, e, n in spans if n == tracing.RUN_SELECTION]
    for s, e, name in spans:
        inside = [o for o in outer if o[0] <= s and e <= o[1]]
        if name in (tracing.RUN_SELECTION_PREPARE,
                    tracing.RUN_SELECTION_FETCH):
            assert len(inside) == 1, (name, s, e, outer)
        elif name == tracing.FUNCTION_INIT:
            # the function is built before the selection starts
            assert not inside, (name, s, e, outer)
    # in each call, the preparation ends before the fetch starts
    prepare = [e for _, e, n in spans if n == tracing.RUN_SELECTION_PREPARE]
    fetch = [s for s, _, n in spans if n == tracing.RUN_SELECTION_FETCH]
    assert all(p <= f for p, f in zip(prepare, fetch))


# -- the re-score trip counter (OptResult.rescore_trips) ---------------------


@pytest.mark.parametrize("mode", ["host", "device"])
def test_dense_and_stochastic_selections_report_no_trips(mode):
    V, _ = _problem(n=128, seed=3)
    f = ExemplarClustering(V, EvalConfig())
    assert greedy(f, 4, mode=mode).rescore_trips == 0
    assert stochastic_greedy(f, 4, mode=mode).rescore_trips == 0


@pytest.mark.parametrize("batch", [8, 32, 500])
@pytest.mark.parametrize("backend", ["jnp", "pallas_interpret"])
def test_device_and_host_lazy_count_the_same_trips(backend, batch):
    V, _ = _problem(n=300, d=16, seed=4)
    f = ExemplarClustering(V, EvalConfig(backend=backend))
    host = lazy_greedy(f, 6, batch=batch, mode="host")
    dev = lazy_greedy(f, 6, batch=batch, mode="device")
    assert dev.indices == host.indices
    assert dev.rescore_trips == host.rescore_trips
    assert dev.evaluations == host.evaluations
    # each round re-scores at least one batch; the bound seeding is no trip
    assert dev.rescore_trips >= 6
    assert dev.evaluations <= f.n + dev.rescore_trips * min(batch, f.n)


def test_batched_lazy_counts_each_requests_trips_as_alone():
    """Ragged k in one bucket: each request's trips are those of its own
    run, and an inert padding slot counts none."""
    fs = [ExemplarClustering(_problem(n=200, d=8, seed=s)[0], EvalConfig())
          for s in (5, 6, 7)]
    ks = [5, 3, 0]
    out = run_selection_batch(fs, kind="lazy", k=5, ks=ks, top_b=16,
                              counter_key="test_batched_trips")
    for f, kb, res in zip(fs, ks, out):
        alone = lazy_greedy(f, kb, batch=16, mode="device")
        assert res.rescore_trips == alone.rescore_trips
        assert res.evaluations == alone.evaluations
    assert out[2].rescore_trips == 0


@pytest.mark.parametrize("mode", ["device_sharded", "device_sharded_pool"])
def test_sharded_plans_count_the_device_trips(mode):
    V, _ = _problem(n=300, d=16, seed=8)
    f = ExemplarClustering(V, EvalConfig())
    dev = lazy_greedy(f, 6, batch=16, mode="device")
    sharded = lazy_greedy(f, 6, batch=16, mode=mode)
    assert sharded.indices == dev.indices
    assert sharded.rescore_trips == dev.rescore_trips
    assert sharded.evaluations == dev.evaluations
