"""Pallas-vs-oracle parity on shapes that exercise the padding path, plus
the precision-policy sweep over the gain kernels.

Unlike test_kernels.py (hypothesis shape sweeps, skipped when the optional
dep is absent), these run unconditionally: ragged ``lengths`` with n, l, d
all *not* divisible by the kernel block sizes, so every pad/mask branch in
``kernels/ops.py`` is hit. The precision sweep runs every kernel parity
check at fp32/bf16/fp16 with per-dtype tolerances, so the half-precision
speedup path (paper §V-B) is exercised in CI instead of only fp32.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import EvalConfig, ExemplarClustering, evaluate_multiset
from repro.core.multiset import PackedMultiset
from repro.core.optimizers import greedy, lazy_greedy
from repro.data.synthetic import blobs

# n, l, k, d chosen indivisible by LANE(128)/SUBLANE(8)/block_n/block_l
RAGGED_SHAPES = [(137, 13, 5, 19), (257, 21, 7, 33), (65, 9, 3, 129)]

# Per-policy tolerance for kernel-vs-jnp AT THE SAME POLICY (both sides
# round inputs/products identically; only reduction/tiling order differs, so
# the band scales with the compute dtype's eps × the blobs problem scale) and
# for policy-vs-fp32 (the paper's §V-B precision-study question: how much
# does half-precision evaluation move the objective?). bf16 keeps 8 mantissa
# bits (eps ≈ 7.8e-3), fp16 has 11 (eps ≈ 9.8e-4), both accumulate fp32.
POLICY_TOLS = {
    "fp32": {"kernel_atol": 1e-5, "vs_fp32_atol": 1e-5},
    "bf16": {"kernel_atol": 5e-2, "vs_fp32_atol": 2e-1},
    "fp16": {"kernel_atol": 1e-2, "vs_fp32_atol": 3e-2},
}


def _ragged_problem(n, l, k, d, seed):
    rng = np.random.default_rng(seed)
    V = jnp.asarray((rng.normal(size=(n, d)) + 2.0).astype(np.float32))
    S = jnp.asarray((rng.normal(size=(l, k, d)) + 2.0).astype(np.float32))
    lengths = jnp.asarray(rng.integers(1, k + 1, size=l).astype(np.int32))
    return V, PackedMultiset(S, lengths)


@pytest.mark.parametrize("shape", RAGGED_SHAPES)
def test_two_pass_pallas_matches_jnp_oracle(shape):
    V, pk = _ragged_problem(*shape, seed=11)
    oracle = np.asarray(evaluate_multiset(V, pk, EvalConfig(mode="two_pass")))
    got = np.asarray(evaluate_multiset(
        V, pk, EvalConfig(mode="two_pass", backend="pallas_interpret")))
    np.testing.assert_allclose(got, oracle, atol=1e-4)


@pytest.mark.parametrize("variant", ["flat", "loop"])
def test_fused_pallas_matches_jnp_oracle_ragged(variant):
    V, pk = _ragged_problem(137, 13, 5, 19, seed=12)
    oracle = np.asarray(evaluate_multiset(V, pk, EvalConfig(mode="fused")))
    got = np.asarray(evaluate_multiset(
        V, pk, EvalConfig(mode="fused", backend="pallas_interpret",
                          kernel_variant=variant)))
    np.testing.assert_allclose(got, oracle, atol=1e-4)


# (n, l, k, d, block_k, lengths) through the wrapper's padding path with
# the loop kernel's k chunk forced small: k zero-padded to whole chunks,
# sets ending inside a chunk or before it, and e0 winning ("far": every
# set one slice far from V).
LOOP_CHUNK_CASES = {
    "k_not_multiple_of_kc": (137, 13, 7, 19, 3, None),
    "k_below_kc": (65, 9, 3, 129, 4, None),
    "lengths_end_mid_chunk": (257, 21, 8, 33, 3, (2, 4, 5, 7, 8)),
    "all_padding_chunk": (97, 11, 6, 17, 2, (1, 2)),
    "singleton_e0_wins": (70, 5, 5, 9, 2, "far"),
}


@pytest.mark.parametrize("policy", ["fp32", "bf16"])
@pytest.mark.parametrize("case", list(LOOP_CHUNK_CASES))
def test_loop_chunks_match_jnp_oracle(case, policy):
    from repro.core.precision import resolve as resolve_policy
    from repro.kernels import ops

    n, l, k, d, block_k, lengths = LOOP_CHUNK_CASES[case]
    V, pk = _ragged_problem(n, l, k, d, seed=14)
    if lengths == "far":
        V = V - 2.0
        pk = PackedMultiset(pk.data + 10.0, jnp.ones((l,), jnp.int32))
    elif lengths is not None:
        pk = PackedMultiset(pk.data, jnp.asarray(
            np.resize(lengths, l).astype(np.int32)))
    cfg = EvalConfig(mode="fused", policy=policy)
    oracle = np.asarray(evaluate_multiset(V, pk, cfg))
    d_e0 = jnp.sum(V ** 2, axis=1)
    got = np.asarray(ops._exemplar_eval_padded(
        V, pk.data, pk.lengths, d_e0, policy=resolve_policy(policy),
        mode="fused", variant="loop", interpret=True, rbf_gamma=None,
        n_total=n, cfgk=ops.KernelConfig(block_n=64, block_l=128,
                                         block_k=block_k)))
    np.testing.assert_allclose(got, oracle, rtol=2e-5,
                               atol=POLICY_TOLS[policy]["kernel_atol"])
    if lengths == "far":
        np.testing.assert_allclose(got, np.mean(np.asarray(d_e0)),
                                   rtol=1e-5)


@pytest.mark.parametrize("policy", sorted(POLICY_TOLS))
def test_gain_kernels_precision_sweep(policy):
    """marginal_gain + fused_gain_update at each PrecisionPolicy: the kernel
    must match the jnp path run at the SAME policy within the dtype band,
    and the policy itself must stay within the precision-study band of the
    fp32 oracle (non-vacuous: distances are computed in the low precision)."""
    from repro.core import distances as dist_mod
    from repro.core.precision import resolve as resolve_policy
    from repro.kernels import ops

    tol = POLICY_TOLS[policy]
    rng = np.random.default_rng(17)
    n, m, d = 133, 41, 21
    V = jnp.asarray((rng.normal(size=(n, d)) + 1.5).astype(np.float32))
    C = V[:m]
    cache = jnp.asarray(rng.uniform(1.0, 5.0, size=n).astype(np.float32))
    w = V[n // 2]
    pol = resolve_policy(policy)
    pair = dist_mod.resolve_pairwise("sqeuclidean")

    def jnp_gains(at):
        D = pair(V, C, at)
        return np.asarray(jnp.sum(
            jnp.maximum(cache[:, None] - D, 0.0), axis=0) / n)

    got = np.asarray(ops.marginal_gain(V, C, cache, policy=pol,
                                       interpret=True))
    np.testing.assert_allclose(got, jnp_gains(pol), atol=tol["kernel_atol"])
    np.testing.assert_allclose(got, jnp_gains(resolve_policy("fp32")),
                               atol=tol["vs_fp32_atol"])

    # fused fold-and-score vs explicit jnp fold + score at the same policy
    dw = pair(V, w[None, :], pol)[:, 0]
    cache_f = jnp.minimum(cache, dw.astype(jnp.float32))
    D = pair(V, C, pol)
    g_ref = np.asarray(jnp.sum(
        jnp.maximum(cache_f[:, None] - D, 0.0), axis=0) / n)
    g, nc = ops.fused_gain_update(V, C, cache, w, policy=pol, interpret=True)
    np.testing.assert_allclose(np.asarray(nc), np.asarray(cache_f),
                               atol=tol["kernel_atol"])
    np.testing.assert_allclose(np.asarray(g), g_ref, atol=tol["kernel_atol"])


@pytest.mark.parametrize("policy", sorted(POLICY_TOLS))
def test_max_template_gain_kernels_precision_sweep(policy):
    """The max-cache template (facility location / graph cut scoring):
    ``relu((α + β·d) − cache)`` with the max fold ``cache ← max(cache, s)``,
    at each PrecisionPolicy. Same dtype bands as the min template — the
    kernel shares one tile loop parameterized by fold direction, so a
    regression in the flipped reduction shows up here and not in the
    exemplar sweep."""
    from repro.core import distances as dist_mod
    from repro.core.functions import SIM_ALPHA, SIM_BETA
    from repro.core.precision import resolve as resolve_policy
    from repro.kernels import ops

    tol = POLICY_TOLS[policy]
    rng = np.random.default_rng(17)
    n, m, d = 133, 41, 21
    # rbf distances keep similarity s = relu(1 − d/2) dense (raw blobs-scale
    # sqeuclidean saturates it to 0 and the max template has nothing to do)
    V = jnp.asarray((rng.normal(size=(n, d)) * 0.3).astype(np.float32))
    C = V[:m]
    cache = jnp.asarray(rng.uniform(0.0, 0.8, size=n).astype(np.float32))
    w = V[n // 2]
    pol = resolve_policy(policy)
    pair = dist_mod.resolve_pairwise("rbf")
    affine = (SIM_ALPHA, SIM_BETA)

    def jnp_gains(cv, at):
        D = pair(V, C, at)
        return np.asarray(jnp.sum(
            jnp.maximum((SIM_ALPHA + SIM_BETA * D) - cv[:, None], 0.0),
            axis=0) / n)

    got = np.asarray(ops.marginal_gain(
        V, C, cache, policy=pol, rbf_gamma=dist_mod.RBF_GAMMA,
        fold="max", score_affine=affine, interpret=True))
    np.testing.assert_allclose(got, jnp_gains(cache, pol),
                               atol=tol["kernel_atol"])
    np.testing.assert_allclose(got, jnp_gains(cache, resolve_policy("fp32")),
                               atol=tol["vs_fp32_atol"])

    # fused max fold-and-score vs explicit jnp fold + score at the policy
    dw = pair(V, w[None, :], pol)[:, 0].astype(jnp.float32)
    cache_f = jnp.maximum(cache, jnp.maximum(SIM_ALPHA + SIM_BETA * dw, 0.0))
    g, nc = ops.fused_gain_update(
        V, C, cache, w, policy=pol, rbf_gamma=dist_mod.RBF_GAMMA,
        fold="max", score_affine=affine, interpret=True)
    np.testing.assert_allclose(np.asarray(nc), np.asarray(cache_f),
                               atol=tol["kernel_atol"])
    np.testing.assert_allclose(np.asarray(g), jnp_gains(cache_f, pol),
                               atol=tol["kernel_atol"])


def test_sieve_gains_max_template_matches_jnp():
    """The sieve table × element kernel under the max template (facility
    location streaming): per-row gains vs the protocol's jnp form, on a
    ragged (r, n) shape that forces the +inf column/row padding — a zero
    pad would score relu(α − t) > 0 against finite rows."""
    from repro.core.functions import FnSpec, sieve_gain_rows
    from repro.kernels import ops

    rng = np.random.default_rng(23)
    r, n = 13, 205
    table = jnp.asarray(rng.uniform(0.0, 1.0, size=(r, n)).astype(np.float32))
    dvec = jnp.asarray(rng.uniform(0.0, 4.0, size=n).astype(np.float32))
    fl = FnSpec(name="facility_location")
    ref = np.asarray(jnp.mean(
        sieve_gain_rows(fl, table, dvec, jnp.zeros(n, jnp.float32)), axis=-1))
    got = np.asarray(ops.sieve_gains(table, dvec, fold="max",
                                     score_affine=(1.0, -0.5),
                                     interpret=True))
    np.testing.assert_allclose(got, ref, atol=1e-6)


@pytest.mark.parametrize("policy", sorted(POLICY_TOLS))
def test_engine_selection_precision_sweep(policy):
    """End-to-end half-precision engine runs: host and device plans must
    still pick identical exemplars at each policy (same kernel scoring, same
    rounding), and the achieved value must sit within the precision-study
    band of the fp32 run."""
    X, _ = blobs(96, 8, centers=4, seed=7)
    fp = ExemplarClustering(
        jnp.asarray(X), EvalConfig(policy=policy, backend="pallas_interpret"))
    f32 = ExemplarClustering(jnp.asarray(X))
    ref = greedy(f32, 4, mode="device")
    host = greedy(fp, 4, mode="host")
    dev = greedy(fp, 4, mode="device")
    assert host.indices == dev.indices
    np.testing.assert_allclose(
        dev.value, ref.value, atol=POLICY_TOLS[policy]["vs_fp32_atol"])
    lh = lazy_greedy(fp, 4, mode="host")
    ld = lazy_greedy(fp, 4, mode="device")
    assert lh.indices == ld.indices
    assert lh.evaluations == ld.evaluations


def test_two_pass_pallas_all_singleton_lengths():
    """Degenerate raggedness: every set has length 1 inside a k=6 buffer."""
    rng = np.random.default_rng(13)
    V = jnp.asarray((rng.normal(size=(97, 17)) + 2.0).astype(np.float32))
    S = jnp.asarray((rng.normal(size=(11, 6, 17)) + 2.0).astype(np.float32))
    pk = PackedMultiset(S, jnp.ones((11,), jnp.int32))
    oracle = np.asarray(evaluate_multiset(V, pk, EvalConfig(mode="two_pass")))
    got = np.asarray(evaluate_multiset(
        V, pk, EvalConfig(mode="two_pass", backend="pallas_interpret")))
    np.testing.assert_allclose(got, oracle, atol=1e-4)
