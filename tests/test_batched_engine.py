"""Batched multi-tenant selection: the batch axis must be invisible.

run_selection_batch solves B independent (V, k) requests in one jitted
dispatch; these tests certify that batching changes throughput and nothing
else — per-request selections, trajectories, AND evaluation counts are
identical to B unbatched run_selection calls across

    strategies {dense, stochastic, lazy}
  × backends {jnp, pallas_interpret}
  × B ∈ {1, 7, 64}

plus ragged per-request k (inert padding slots included), the B-aware gain
tile autotuner, and the donated-carry buffer discipline.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import EvalConfig, run_selection, run_selection_batch
from repro.core import engine as eng
from repro.core.functions import FUNCTIONS
from repro.core.optimizers import stochastic_greedy
from repro.core.service import _stochastic_samples
from repro.data.synthetic import blobs

N, D, K = 48, 8, 3
EPS = 0.1
BACKENDS = ("jnp", "pallas_interpret")
TRAJ_ATOL = {"jnp": 1e-5, "pallas_interpret": 1e-4}
N_DISTINCT = 6  # B > 6 cycles these tenants; duplicates must agree too

_FUNCS: dict = {}


def _funcs(backend: str, fname: str = "exemplar"):
    key = (backend, fname)
    if key not in _FUNCS:
        cfg = EvalConfig(backend=backend)
        _FUNCS[key] = [
            FUNCTIONS[fname](
                jnp.asarray(blobs(N, D, centers=4, seed=70 + t)[0]), cfg)
            for t in range(N_DISTINCT)]
    return _FUNCS[key]


def _ref(f, kind: str, k: int, seed: int):
    """Unbatched engine reference for one request."""
    if kind == "stochastic":
        return stochastic_greedy(f, k, eps=EPS, seed=seed, mode="device")
    cand = np.arange(f.n, dtype=np.int32)[None, :] if kind == "dense" \
        else None
    return run_selection(f, kind=kind, k=k, cand_rounds=cand,
                         counter_key=f"test_ref_{kind}")


@pytest.mark.parametrize("B", [1, 7, 64])
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("kind", ["dense", "stochastic", "lazy"])
def test_batched_matches_unbatched(kind, backend, B):
    distinct = _funcs(backend)
    tenants = [t % N_DISTINCT for t in range(B)]
    fs = [distinct[t] for t in tenants]
    cand = None
    if kind == "stochastic":
        # the serving layer's draw is bit-identical to stochastic_greedy's
        cand = np.stack(
            [_stochastic_samples(N, K, EPS, seed=t) for t in tenants])
    res = run_selection_batch(fs, kind=kind, k=K, cand_rounds=cand,
                              counter_key=f"test_batched_{kind}")
    refs = {t: _ref(distinct[t], kind, K, t) for t in set(tenants)}
    assert len(res) == B
    for b, t in enumerate(tenants):
        ref = refs[t]
        assert res[b].indices == ref.indices, (kind, backend, B, b)
        assert res[b].evaluations == ref.evaluations, (kind, backend, B, b)
        np.testing.assert_allclose(
            res[b].trajectory, ref.trajectory, atol=TRAJ_ATOL[backend],
            err_msg=f"{kind}/{backend}/B={B} request {b}")


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("kind", ["dense", "lazy"])
def test_batched_ragged_k(kind, backend):
    """Per-request k ≤ scan length: request b freezes after ks[b] rounds
    and gets exactly the unbatched k=ks[b] result; ks[b]=0 slots (bucket
    padding) are inert."""
    ks = [5, 2, 0, 3, 1]
    distinct = _funcs(backend)
    fs = [distinct[b % N_DISTINCT] for b in range(len(ks))]
    res = run_selection_batch(fs, kind=kind, k=max(ks), ks=ks,
                              counter_key=f"test_ragged_{kind}")
    for b, kb in enumerate(ks):
        if kb == 0:
            assert res[b].indices == [] and res[b].evaluations == 0
            continue
        ref = _ref(fs[b], kind, kb, b)
        assert res[b].indices == ref.indices, (kind, backend, b)
        assert res[b].evaluations == ref.evaluations, (kind, backend, b)
        np.testing.assert_allclose(
            res[b].trajectory, ref.trajectory, atol=TRAJ_ATOL[backend])


def test_batched_celf_per_request_eval_counts():
    """Lazy-CELF carries per-request bound state: tenants with different
    data do different amounts of re-scoring, and each request's evaluation
    count must equal its own unbatched CELF run — not a batch-wide
    maximum. (The counts differing ACROSS tenants is what makes this a
    real per-request test.)"""
    distinct = _funcs("jnp")
    # a narrow re-score width (top_b=8) over more rounds makes per-tenant
    # certification behavior actually diverge at this problem size
    res = run_selection_batch(distinct, kind="lazy", k=5, top_b=8,
                              counter_key="test_celf_counts")
    counts = [r.evaluations for r in res]
    refs = [run_selection(f, kind="lazy", k=5, top_b=8,
                          counter_key="test_celf_counts_ref")
            for f in distinct]
    assert counts == [r.evaluations for r in refs]
    assert len(set(counts)) > 1, (
        "test data degenerated: every tenant re-scored identically, so "
        "per-request bound state is not actually exercised")


def test_batched_function_axis():
    """The zoo stays batch-transparent: graph_cut's scalar aux and
    saturated_coverage's per-row caps ride the batch axis unchanged."""
    for fname, params in (("graph_cut", {"lam": 0.5}),
                          ("saturated_coverage", {"sat": 0.25})):
        cfg = EvalConfig(distance="rbf")
        fs = [FUNCTIONS[fname](
            jnp.asarray(blobs(N, D, centers=4, seed=70 + t)[0]) / 10.0,
            cfg, **params) for t in range(4)]
        res = run_selection_batch(fs, kind="dense", k=K,
                                  counter_key=f"test_zoo_{fname}")
        for b, f in enumerate(fs):
            ref = run_selection(
                f, kind="dense", k=K,
                cand_rounds=np.arange(N, dtype=np.int32)[None, :],
                counter_key=f"test_zoo_ref_{fname}")
            assert res[b].indices == ref.indices, (fname, b)
            assert res[b].evaluations == ref.evaluations, (fname, b)
            np.testing.assert_allclose(
                res[b].trajectory, ref.trajectory, atol=1e-5)


# ---------------------------------------------------------------------------
# Satellite: the gain-tile autotuner must account for the batch axis
# ---------------------------------------------------------------------------


def test_device_block_m_scales_with_batch(monkeypatch):
    """The live batched footprint is (B·n, B·m): a B=1024 bucket sized as
    if B=1 would over-commit memory 1024× (the forced-host failure mode of
    PR 5, now on the batch axis)."""
    monkeypatch.setattr(eng, "_GAIN_TILE_CAP_ELEMS", 1 << 25)
    # fits unbatched: cap 2^25 elems, tile 2^20 × 64 = 2^26 → block 32
    assert eng._device_block_m(1 << 20, 64) == 32
    # the same per-request shape under B=8 must shrink 8× further (floor 8)
    assert eng._device_block_m(1 << 20, 64, n_batch=8) == 8
    # a serving-sized bucket: n=1024, m=1024 fits alone (2^20 ≤ 2^25) ...
    assert eng._device_block_m(1024, 1024) == 1024
    # ... but B=64 tenants make rows = 2^16 → 2^25 // 2^16 = 512
    assert eng._device_block_m(1024, 1024, n_batch=64) == 512
    # degenerate n_batch values behave like B=1
    assert eng._device_block_m(1024, 1024, n_batch=0) == 1024


def test_run_selection_batch_sizes_tiles_for_batch(monkeypatch):
    """run_selection_batch must pass n_batch=B into the autotuner — a
    sizing spy, so a future refactor that drops the argument fails here
    rather than OOMing at B=1024 in production."""
    calls = []
    real = eng._device_block_m

    def spy(n, m, tiles_per_memory=1, n_batch=1):
        calls.append({"n": n, "m": m, "n_batch": n_batch})
        return real(n, m, tiles_per_memory, n_batch)

    monkeypatch.setattr(eng, "_device_block_m", spy)
    fs = _funcs("jnp")[:4]
    run_selection_batch(fs, kind="dense", k=2, counter_key="test_spy")
    assert calls and calls[-1]["n_batch"] == 4 and calls[-1]["n"] == N


# ---------------------------------------------------------------------------
# Satellite: donated scan carry — warm-bucket serving must not churn
# ---------------------------------------------------------------------------


def test_dispatch_donates_seed_and_preserves_function_state():
    """Both jitted dispatches donate the cache seed (it aliases the final
    folded cache output). The donated buffer must be a COPY: the
    function's resident cache_seed / d_e0 stay alive, and repeated
    same-signature calls (warm-bucket serving) return identical results."""
    f = _funcs("jnp")[0]
    cand = np.arange(N, dtype=np.int32)[None, :]
    r1 = run_selection(f, kind="dense", k=K, cand_rounds=cand,
                       counter_key="test_donate")
    assert not f.cache_seed.is_deleted()
    assert not f.d_e0.is_deleted()
    r2 = run_selection(f, kind="dense", k=K, cand_rounds=cand,
                       counter_key="test_donate")
    assert r1.indices == r2.indices and r1.trajectory == r2.trajectory

    fs = _funcs("jnp")[:4]
    b1 = run_selection_batch(fs, kind="dense", k=K,
                             counter_key="test_donate_b")
    b2 = run_selection_batch(fs, kind="dense", k=K,
                             counter_key="test_donate_b")
    assert all(not g.cache_seed.is_deleted() for g in fs)
    assert all(x.indices == y.indices for x, y in zip(b1, b2))


def test_batched_dispatch_consumes_its_seed():
    """The donation is real: the freshly-stacked seed buffer handed to the
    batched dispatch is deleted after the call (aliased onto the final
    cache output), not silently copied."""
    fs = _funcs("jnp")[:2]
    seed_b = jnp.asarray(
        np.stack([np.asarray(g.cache_seed, np.float32) for g in fs]))
    V_b = jnp.asarray(np.stack([np.asarray(g.V) for g in fs]))
    aux_b = jnp.asarray(np.stack([np.asarray(g.row_aux) for g in fs]))
    w0_b = jnp.zeros((2, D), V_b.dtype)
    cand = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32)[None, None, :],
                            (2, 1, N))
    eng._select_scan(
        V_b, seed_b, aux_b, cand, w0_b, jnp.asarray([K, K], jnp.int32),
        fn=fs[0].spec, kind="dense", k=K, top_b=0,
        distance=fs[0].cfg.distance, policy_name="fp32", block_m=N,
        backend="jnp", rbf_gamma=None, counter_key="test_donate_direct")
    assert seed_b.is_deleted()
    assert not V_b.is_deleted()


# ---------------------------------------------------------------------------
# One-dispatch property: B requests must not multiply traces
# ---------------------------------------------------------------------------


def test_batched_is_one_trace_per_signature():
    """Two same-signature batched calls = one trace; the second call hits
    the warm jit cache (the serving layer's whole reason for bucketing)."""
    key = "test_trace_count_batched"
    fs = _funcs("jnp")[:4]
    run_selection_batch(fs, kind="dense", k=K, counter_key=key)
    assert eng.DEVICE_TRACE_COUNTS[key] == 1
    run_selection_batch(fs, kind="dense", k=K, counter_key=key)
    assert eng.DEVICE_TRACE_COUNTS[key] == 1
    # a different B is a different signature — exactly one more trace
    run_selection_batch(fs[:2], kind="dense", k=K, counter_key=key)
    assert eng.DEVICE_TRACE_COUNTS[key] == 2


# ---------------------------------------------------------------------------
# Guard rails
# ---------------------------------------------------------------------------


def test_batched_rejects_mixed_signatures():
    fs = _funcs("jnp")
    other_shape = FUNCTIONS["exemplar"](
        jnp.asarray(blobs(N * 2, D, centers=4, seed=1)[0]))
    with pytest.raises(ValueError, match="payload shape"):
        run_selection_batch([fs[0], other_shape], kind="dense", k=2,
                            counter_key="test_guard")
    other_cfg = FUNCTIONS["exemplar"](
        fs[0].V, EvalConfig(backend="pallas_interpret"))
    with pytest.raises(ValueError, match="EvalConfig"):
        run_selection_batch([fs[0], other_cfg], kind="dense", k=2,
                            counter_key="test_guard")
    gc = FUNCTIONS["graph_cut"](fs[0].V)
    with pytest.raises(ValueError, match="function spec"):
        run_selection_batch([fs[0], gc], kind="dense", k=2,
                            counter_key="test_guard")


def test_batched_rejects_bad_ks():
    fs = _funcs("jnp")[:2]
    with pytest.raises(ValueError, match="ks has"):
        run_selection_batch(fs, kind="dense", k=2, ks=[2],
                            counter_key="test_guard")
    with pytest.raises(ValueError, match=r"\[0, 2\]"):
        run_selection_batch(fs, kind="dense", k=2, ks=[2, 3],
                            counter_key="test_guard")
    assert run_selection_batch(fs, kind="dense", k=2, ks=[0, 0],
                               counter_key="test_guard") \
        == [eng.OptResult([], 0.0, [], 0)] * 2
