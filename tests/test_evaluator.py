"""Evaluation-engine equivalence: modes × backends × chunking × precision."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ChunkingError, EvalConfig, ExemplarClustering,
                        bytes_per_set, evaluate_multiset, pack_sets,
                        plan_chunks, work_matrix)
from repro.core import distances as dist_mod
from repro.core.evaluator import e0_distances
from repro.core.precision import FP16_STRICT, FP32


@pytest.fixture(scope="module")
def problem():
    rng = np.random.default_rng(3)
    V = jnp.asarray((rng.normal(size=(257, 33)) + 2.0).astype(np.float32))
    sets = [np.asarray(V[rng.choice(257, size=rng.integers(1, 9),
                                    replace=False)]) for _ in range(19)]
    return V, pack_sets(sets)


def _vals(V, pk, **kw):
    return np.asarray(evaluate_multiset(V, pk, EvalConfig(**kw)))


def test_fused_equals_two_pass(problem):
    V, pk = problem
    np.testing.assert_allclose(_vals(V, pk, mode="fused"),
                               _vals(V, pk, mode="two_pass"), atol=1e-5)


def test_fused_equals_naive_alg2(problem):
    """The engine reproduces paper Algorithm 2 exactly."""
    V, pk = problem
    np.testing.assert_allclose(_vals(V, pk), _vals(V, pk, backend="naive"),
                               atol=1e-5)


def test_chunked_equals_unchunked(problem):
    V, pk = problem
    mu = bytes_per_set(V.shape[0], pk.k_max, pk.dim, FP32, "fused")
    for budget in (mu * 3, mu * 7, mu * 100):
        np.testing.assert_allclose(
            _vals(V, pk, memory_budget_bytes=int(budget)), _vals(V, pk),
            atol=1e-5)


def test_chunk_plan_formula(problem):
    """n_chunks = ⌈l / ⌊φ/μ_s⌋⌉ (paper §IV-B-3)."""
    V, pk = problem
    mu = bytes_per_set(V.shape[0], pk.k_max, pk.dim, FP32, "fused")
    chunks = plan_chunks(19, V.shape[0], pk.k_max, pk.dim, FP32, "fused",
                         mu * 5)
    assert len(chunks) == int(np.ceil(19 / 5))
    assert chunks[0] == (0, 5) and chunks[-1][1] == 19


def test_chunking_failure_raises(problem):
    V, pk = problem
    with pytest.raises(ChunkingError, match="lower floating-point"):
        plan_chunks(19, V.shape[0], pk.k_max, pk.dim, FP32, "fused", 10)


def test_auto_budget_resolves_via_probe(problem, monkeypatch):
    """"auto" budget goes through the free-memory probe; probeless backends
    (CPU) degrade to unchunked, a probed value yields the probed chunking."""
    from repro.core import evaluator as ev

    V, pk = problem
    monkeypatch.setattr(ev, "_AUTO_BUDGET_BYTES", False)
    monkeypatch.setattr(ev, "free_memory_bytes", lambda device=None: None)
    assert plan_chunks(19, V.shape[0], pk.k_max, pk.dim, FP32, "fused",
                       "auto") == [(0, 19)]
    mu = bytes_per_set(V.shape[0], pk.k_max, pk.dim, FP32, "fused")
    monkeypatch.setattr(ev, "free_memory_bytes",
                        lambda device=None: int(4 * mu / ev.AUTO_BUDGET_FRACTION))
    # probe frozen at first use: a changed probe must NOT move the chunking
    assert plan_chunks(19, V.shape[0], pk.k_max, pk.dim, FP32, "fused",
                       "auto") == [(0, 19)]
    monkeypatch.setattr(ev, "_AUTO_BUDGET_BYTES", False)  # re-probe
    chunks = plan_chunks(19, V.shape[0], pk.k_max, pk.dim, FP32, "fused",
                         "auto")
    assert chunks == plan_chunks(19, V.shape[0], pk.k_max, pk.dim, FP32,
                                 "fused", 4 * mu)
    assert len(chunks) == 5  # ⌈19/4⌉


def test_device_block_m_uses_probe(monkeypatch):
    """The engine's candidate block size derives from the same probe; the
    probed cap freezes at first use so jit statics can't churn per call."""
    from repro.core import engine as eng

    monkeypatch.setattr(eng, "_GAIN_TILE_CAP_ELEMS", None)
    monkeypatch.setattr(eng, "free_memory_bytes", lambda device=None: None)
    assert eng._device_block_m(1 << 20, 64) == 32  # 128 MiB fallback cap
    monkeypatch.setattr(eng, "free_memory_bytes",
                        lambda device=None: 1 << 30)  # 1 GiB free
    # cap already frozen: a changed probe must NOT change the block size
    assert eng._device_block_m(1 << 20, 64) == 32
    monkeypatch.setattr(eng, "_GAIN_TILE_CAP_ELEMS", None)
    assert eng._device_block_m(1 << 20, 64) == 64  # re-probed: tile fits

    from repro.core import evaluator as ev
    monkeypatch.setattr(ev, "_AUTO_BUDGET_BYTES", False)
    monkeypatch.setattr(ev, "free_memory_bytes", lambda device=None: 0)
    assert ev.resolve_memory_budget("auto") == 0  # 0 free ≠ "no budget"


def test_device_block_m_mesh_aware_sizing(monkeypatch):
    """Regression (sharded autotuning): the gain tile must be sized from
    the LOCAL shard height n/p — sizing from global n under-fills every
    shard p× — and, when p shards' tiles coexist in one physical memory
    space (forced host devices share the allocator the probe measured),
    the cap must divide by p or the shards jointly over-commit it."""
    import jax

    from repro.core import engine as eng

    monkeypatch.setattr(eng, "_GAIN_TILE_CAP_ELEMS", None)
    monkeypatch.setattr(eng, "free_memory_bytes", lambda device=None: None)
    # one tile per memory: 2^25 fallback cap over a 2^20-row tile → 32 wide
    assert eng._device_block_m(1 << 20, 64) == 32
    # 4 coexisting tiles: each gets a quarter of the cap → 8 wide
    assert eng._device_block_m(1 << 20, 64, tiles_per_memory=4) == 8
    # …but sized from the LOCAL height n/4, the same global problem fits
    # the exact same 32-wide tile per shard — under-filling fixed
    assert eng._device_block_m((1 << 20) // 4, 64, tiles_per_memory=4) == 32

    # forced host devices share one memory space; a real accelerator mesh
    # reports 1 tile per device memory
    mesh = jax.make_mesh((jax.device_count(),), ("data",))
    expected = jax.device_count() \
        if jax.local_devices()[0].platform == "cpu" else 1
    assert eng.mesh_tiles_per_memory(mesh) == expected


def test_sharded_selection_sizes_tile_from_local_height(monkeypatch):
    """End to end: run_sharded_selection must hand the autotuner the local
    shard height (n_pad/p) and the mesh's tiles-per-memory count."""
    import jax
    import jax.numpy as jnp

    from repro.core import distributed as dist
    from repro.core.functions import ExemplarClustering
    from repro.core.optimizers import greedy

    calls = []
    real = dist._device_block_m

    def spy(n, m, tiles=1):
        calls.append((n, m, tiles))
        return real(n, m, tiles)

    monkeypatch.setattr(dist, "_device_block_m", spy)
    rng = np.random.default_rng(3)
    V = jnp.asarray((rng.normal(size=(250, 8)) + 2).astype(np.float32))
    f = ExemplarClustering(V)
    greedy(f, 3, mode="device_sharded")
    ndev = jax.device_count()
    n_loc = -(-250 // ndev)
    assert calls == [(n_loc, 250, ndev)], calls


def test_device_block_m_batch_mesh_composition(monkeypatch):
    """Regression (batched × sharded autotuning): when BOTH factors apply,
    the tile must be sized from B·n_loc rows — the (B, n/p) slab a shard
    actually scores — with the shared-memory-space cap divided ONCE by the
    coexisting-tile count. Sizing from B·n GLOBAL rows (or dividing the cap
    again per factor) under-fills every shard p×."""
    from repro.core import engine as eng

    monkeypatch.setattr(eng, "_GAIN_TILE_CAP_ELEMS", None)
    monkeypatch.setattr(eng, "free_memory_bytes", lambda device=None: None)
    # fallback cap 2^25 elems, 4 coexisting tiles → 2^23 each; B·n_loc =
    # 4·2^16 = 2^18 rows → a 32-wide tile fits exactly
    assert eng._device_block_m(1 << 16, 64, tiles_per_memory=4,
                               n_batch=4) == 32
    # the regression shapes: sized from B·n GLOBAL (n = n_loc·p = 2^18)
    # the same problem collapses to an 8-wide tile — 4× under-filled
    assert eng._device_block_m((1 << 16) * 4, 64, tiles_per_memory=4,
                               n_batch=4) == 8
    # each factor alone reduces to the already-pinned single-axis behavior
    assert eng._device_block_m(1 << 18, 64, n_batch=4) == \
        eng._device_block_m(1 << 20, 64)
    assert eng._device_block_m(1 << 16, 64, tiles_per_memory=4) == 64


def test_batched_sharded_selection_sizes_tile_from_local_height(monkeypatch):
    """End to end: run_selection_batch on a sharded plan must hand the
    autotuner (n_loc, m_widest, tiles_per_memory, B) — local shard height
    AND batch width, cap split once by the mesh."""
    import jax
    import jax.numpy as jnp

    from repro.core import distributed as dist
    from repro.core.engine import run_selection_batch
    from repro.core.functions import ExemplarClustering

    calls = []
    real = dist._device_block_m

    def spy(n, m, tiles=1, n_batch=1):
        calls.append((n, m, tiles, n_batch))
        return real(n, m, tiles, n_batch=n_batch)

    monkeypatch.setattr(dist, "_device_block_m", spy)
    rng = np.random.default_rng(3)
    fs = [ExemplarClustering(
              jnp.asarray((rng.normal(size=(250, 8)) + 2).astype(np.float32)))
          for _ in range(3)]
    run_selection_batch(fs, kind="dense", k=3, plan="device_sharded",
                        counter_key="eval_spy_bsh")
    ndev = jax.device_count()
    n_loc = -(-250 // ndev)
    assert calls == [(n_loc, 250, ndev, 3)], calls


def test_fp16_strict_reduces_mu():
    """The paper's remediation: FP16 shrinks the per-set footprint."""
    assert bytes_per_set(1000, 10, 100, FP16_STRICT, "fused") < \
        bytes_per_set(1000, 10, 100, FP32, "fused")


def test_nblock_streaming_equals(problem):
    V, pk = problem
    np.testing.assert_allclose(_vals(V, pk, n_block=64), _vals(V, pk),
                               atol=1e-5)


def test_work_matrix_shape_and_reduction(problem):
    """W (l, n) row-reduces to the same values (paper eq. 7)."""
    V, pk = problem
    W = work_matrix(V, pk)
    assert W.shape == (19, 257)
    np.testing.assert_allclose(np.asarray(W.sum(axis=1)), _vals(V, pk),
                               atol=1e-5)


@pytest.mark.parametrize("policy,tol", [("bf16", 2e-2), ("fp16", 5e-3),
                                        ("fp16_strict", 5e-2)])
def test_low_precision_drift_bounded(problem, policy, tol):
    V, pk = problem
    ref = _vals(V, pk)
    got = _vals(V, pk, policy=policy)
    rel = np.max(np.abs(got - ref) / np.maximum(np.abs(ref), 1e-6))
    assert rel < tol


@pytest.mark.parametrize("distance", ["sqeuclidean", "manhattan", "cosine",
                                      "rbf"])
def test_distances_match_naive(problem, distance):
    V, pk = problem
    np.testing.assert_allclose(
        _vals(V, pk, distance=distance),
        _vals(V, pk, distance=distance, backend="naive"), atol=1e-4)


# ---------------------------------------------------------------------------
# The e0 column: one compiled program, the same math as the direct definition
# ---------------------------------------------------------------------------

#: (rtol, atol) of the e0 column against ``POINT`` on the float32 inputs:
#: bf16 rounds V and e0 to 8 mantissa bits before the Gram expansion.
E0_TOL = {"fp32": (1e-5, 1e-5), "bf16": (2e-2, 2e-2)}


def _e0(problem, given):
    V, _ = problem
    if not given:
        return None
    rng = np.random.default_rng(11)
    return jnp.asarray(rng.normal(size=(V.shape[1],)).astype(np.float32))


@pytest.mark.parametrize("given", [False, True], ids=["e0_none", "e0_given"])
@pytest.mark.parametrize("policy", sorted(E0_TOL))
@pytest.mark.parametrize("distance", sorted(dist_mod.PAIRWISE))
def test_e0_column_matches_point_definition(problem, distance, policy, given):
    V, _ = problem
    e0 = _e0(problem, given)
    got = np.asarray(e0_distances(V, e0, distance, policy))
    point = jax.vmap(dist_mod.POINT[distance], in_axes=(0, None))
    ref = np.asarray(point(V, jnp.zeros((V.shape[1],)) if e0 is None else e0))
    rtol, atol = E0_TOL[policy]
    assert got.shape == (V.shape[0],)
    np.testing.assert_allclose(got, ref, rtol=rtol, atol=atol)


@pytest.mark.parametrize("given", [False, True], ids=["e0_none", "e0_given"])
@pytest.mark.parametrize("policy", sorted(E0_TOL))
@pytest.mark.parametrize("distance", sorted(dist_mod.PAIRWISE))
def test_exemplar_L0_is_the_mean_of_its_e0_column(problem, distance, policy,
                                                  given):
    V, _ = problem
    e0 = _e0(problem, given)
    f = ExemplarClustering(V, EvalConfig(distance=distance, policy=policy),
                           e0=e0)
    assert isinstance(f.L0, float)
    column = np.asarray(f.d_e0, np.float64)
    np.testing.assert_allclose(f.L0, column.mean(), rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(f.d_e0), np.asarray(e0_distances(V, e0, distance, policy)))


@pytest.mark.parametrize("backend", ["jnp", "pallas_interpret"])
def test_warm_e0_column_dispatches_no_eager_primitive(problem, monkeypatch,
                                                      backend):
    """A warm ``evaluate_multiset`` with no ``d_e0`` runs its e0 column as
    one compiled program: it sends no primitive through JAX's eager
    primitive dispatch, ``jax._src.dispatch.apply_primitive``, which looks
    up each primitive's executable through
    ``jax._src.dispatch.xla_primitive_callable`` (the hook counted here).
    So the call dispatches exactly what the same call with ``d_e0`` given
    does."""
    from jax._src import dispatch

    V, pk = problem
    cfg = EvalConfig(backend=backend)
    d_e0 = e0_distances(V, None, cfg.distance, cfg.policy)
    for kw in ({}, {"d_e0": d_e0}):  # compile every program once
        evaluate_multiset(V, pk, cfg, **kw).block_until_ready()
    seen = []
    real = dispatch.xla_primitive_callable

    def counting(prim, **params):
        seen.append(prim.name)
        return real(prim, **params)

    monkeypatch.setattr(dispatch, "xla_primitive_callable", counting)
    e0_distances(V, None, cfg.distance, cfg.policy).block_until_ready()
    assert seen == []
    evaluate_multiset(V, pk, cfg).block_until_ready()
    without_d_e0 = list(seen)
    seen.clear()
    evaluate_multiset(V, pk, cfg, d_e0=d_e0).block_until_ready()
    assert without_d_e0 == seen


# ---------------------------------------------------------------------------
# Construction-time validation: a bad config or function parameter must fail
# when it is built, not deep inside the first traced dispatch.
# ---------------------------------------------------------------------------


def test_evalconfig_validates_at_construction():
    with pytest.raises(ValueError, match="unknown distance"):
        EvalConfig(distance="hamming")
    with pytest.raises(ValueError, match="mode"):
        EvalConfig(mode="three_pass")
    with pytest.raises(ValueError, match="unknown backend"):
        EvalConfig(backend="tpu")
    with pytest.raises(ValueError, match="kernel_variant"):
        EvalConfig(kernel_variant="nested")
    with pytest.raises(ValueError, match="policy"):
        EvalConfig(policy="fp8")
    with pytest.raises(ValueError, match="memory_budget_bytes"):
        EvalConfig(memory_budget_bytes="lots")


def test_function_parameters_validate_at_construction():
    """Graph cut's λ and saturated coverage's cap fraction gate the zoo's
    monotonicity/submodularity guarantees — out-of-range values must refuse
    before any cache exists."""
    from repro.core import GraphCut, SaturatedCoverage

    rng = np.random.default_rng(0)
    V = jnp.asarray(rng.normal(size=(16, 4)).astype(np.float32))
    for lam in (0.0, -0.1, 0.6, 2.0):
        with pytest.raises(ValueError, match="lam"):
            GraphCut(V, lam=lam)
    for sat in (0.0, -0.5, 1.5):
        with pytest.raises(ValueError, match="sat"):
            SaturatedCoverage(V, sat=sat)
    assert GraphCut(V, lam=0.5).spec.lam == 0.5
    assert SaturatedCoverage(V, sat=1.0).spec.sat == 1.0
