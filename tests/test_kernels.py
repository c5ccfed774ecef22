"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles.

Every kernel runs in interpret mode (bit-accurate Python execution of the
kernel body) against ref.py across problem shapes, layouts, modes, dtypes.
"""
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="optional test extra; pip install .[test]")
from hypothesis import given, settings, strategies as st

from repro.core.precision import BF16, FP16, FP16_STRICT, FP32
from repro.kernels import ops, ref

POLICIES = {"fp32": FP32, "bf16": BF16, "fp16": FP16,
            "fp16_strict": FP16_STRICT}


def _problem(n, l, k, d, seed=0):
    rng = np.random.default_rng(seed)
    V = jnp.asarray((rng.normal(size=(n, d)) + 2.0).astype(np.float32))
    S = jnp.asarray((rng.normal(size=(l, k, d)) + 2.0).astype(np.float32))
    lengths = jnp.asarray(rng.integers(1, k + 1, size=l).astype(np.int32))
    d_e0 = jnp.sum(V.astype(jnp.float32) ** 2, axis=1)
    return V, S, lengths, d_e0


SHAPES = [(64, 8, 3, 16), (257, 19, 7, 33), (512, 64, 10, 100),
          (100, 5, 1, 128), (96, 24, 16, 200)]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("variant", ["flat", "loop"])
def test_fused_vs_oracle(shape, variant):
    V, S, lengths, d_e0 = _problem(*shape)
    want = ref.exemplar_eval_ref(V, S, lengths, d_e0)
    got = ops.exemplar_eval(V, S, lengths, d_e0, mode="fused",
                            variant=variant, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", SHAPES[:3])
def test_two_pass_vs_oracle(shape):
    V, S, lengths, d_e0 = _problem(*shape)
    want = ref.exemplar_eval_ref(V, S, lengths, d_e0)
    got = ops.exemplar_eval(V, S, lengths, d_e0, mode="two_pass",
                            interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("pname", list(POLICIES))
def test_dtype_sweep_matches_matching_policy_oracle(pname):
    policy = POLICIES[pname]
    V, S, lengths, d_e0 = _problem(128, 16, 5, 48, seed=4)
    want = ref.exemplar_eval_ref(V, S, lengths, d_e0, policy=policy)
    got = ops.exemplar_eval(V, S, lengths, d_e0, policy=policy,
                            interpret=True)
    tol = 1e-5 if pname == "fp32" else 3e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


def test_rbf_kernel_distance():
    V, S, lengths, d_e0 = _problem(96, 12, 4, 32, seed=5)
    d_e0r = 2.0 * (1.0 - jnp.exp(-d_e0))
    want = ref.exemplar_eval_ref(V, S, lengths, d_e0r, rbf_gamma=1.0)
    got = ops.exemplar_eval(V, S, lengths, d_e0r, rbf_gamma=1.0,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_chunked_kernel_equals_unchunked():
    V, S, lengths, d_e0 = _problem(128, 32, 6, 40, seed=6)
    full = ops.exemplar_eval(V, S, lengths, d_e0, interpret=True)
    chunked = ops.exemplar_eval(V, S, lengths, d_e0, interpret=True,
                                memory_budget_bytes=400_000)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(full),
                               atol=1e-6)


@given(n=st.integers(9, 150), m=st.integers(1, 40), d=st.integers(1, 70))
@settings(max_examples=12, deadline=None)
def test_marginal_gain_property_shapes(n, m, d):
    rng = np.random.default_rng(n * 1000 + m * 10 + d)
    V = jnp.asarray((rng.normal(size=(n, d)) + 1.0).astype(np.float32))
    C = jnp.asarray((rng.normal(size=(m, d)) + 1.0).astype(np.float32))
    cache = jnp.asarray(rng.uniform(0.5, 4.0, size=n).astype(np.float32))
    want = ref.marginal_gain_ref(V, C, cache)
    got = ops.marginal_gain(V, C, cache, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
    assert np.all(np.asarray(got) >= -1e-6)  # gains are non-negative


def test_kernel_config_respects_budget():
    """The b_x/b_y analogue: S tile obeys the VMEM budget (paper eq. for b_x)."""
    from repro.kernels.ops import VMEM_S_BUDGET, kernel_config
    for k, d_pad in [(1, 128), (10, 128), (500, 128), (500, 256)]:
        cfgk = kernel_config(k, d_pad, FP32, l=100_000, n=100_000)
        assert cfgk.block_l * k * d_pad * 4 <= max(
            VMEM_S_BUDGET, 8 * k * d_pad * 4)  # ≥ SUBLANE rows always allowed
        assert cfgk.block_l % 8 == 0 and cfgk.block_n % 8 == 0


def test_kernel_config_refuses_flat_tile_beyond_vmem():
    """The flat variant keeps all k distance slices live: past k≈38 at fp32
    no lane-dense tile fits, and it says so instead of handing Mosaic a
    tile it refuses; the one-slice variants still find one."""
    from repro.kernels.ops import kernel_config
    with pytest.raises(ValueError, match="VMEM.*'loop'"):
        kernel_config(45, 128, FP32, l=5_000, n=50_000, variant="flat")
    for variant in ("loop", "two_pass"):
        cfgk = kernel_config(500, 128, FP32, l=5_000, n=50_000,
                             variant=variant)
        assert cfgk.block_l % 8 == 0 and cfgk.block_n % 128 == 0
        cfgk = kernel_config(500, 128, BF16, l=5_000, n=50_000,
                             variant=variant)
        assert cfgk.block_l % 16 == 0 and cfgk.block_n % 128 == 0


def test_flat_past_vmem_runs_as_loop():
    """exemplar_eval's default flat variant has no tile at k=80 (n=64):
    it runs the loop kernel instead and still matches the oracle."""
    from repro.kernels.ops import kernel_config
    V, S, lengths, d_e0 = _problem(64, 8, 80, 8, seed=7)
    with pytest.raises(ValueError, match="VMEM"):
        kernel_config(80, 128, FP32, l=8, n=64, variant="flat")
    want = ref.exemplar_eval_ref(V, S, lengths, d_e0)
    got = ops.exemplar_eval(V, S, lengths, d_e0, mode="fused",
                            variant="flat", interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_grid_covers_problem():
    """Paper eq. 8: the grid tiles the whole work matrix."""
    from repro.kernels.ops import kernel_config, _round_up
    cfgk = kernel_config(10, 128, FP32, l=1000, n=50_000)
    l_pad = _round_up(1000, cfgk.block_l)
    n_pad = _round_up(50_000, cfgk.block_n)
    gl, gn = cfgk.grid(n_pad, l_pad)
    assert gl * cfgk.block_l >= 1000 and gn * cfgk.block_n >= 50_000
