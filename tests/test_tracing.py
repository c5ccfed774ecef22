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
                        greedy, pack_sets, tracing)
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


def _traced_selections(tmp_path, V, cfg, k=3):
    greedy(ExemplarClustering(V, cfg), k, mode="device")  # compile
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(CALLS):
            greedy(ExemplarClustering(V, cfg), k, mode="device")
    return _spans(tmp_path)


@pytest.mark.parametrize("backend", ["jnp", "pallas_interpret"])
def test_selection_spans_open_once_per_call(tmp_path, backend):
    V, _ = _problem(seed=2)
    spans = _traced_selections(tmp_path, V, EvalConfig(backend=backend))
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
