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


#: the gain kernels' tile at n = m = 50,000, d_pad = 128, fp32: the
#: fastest of the (Bn, Bm) sweep timed on a TPU v5e
GREEDY_CELL_TILE = (1024, 2048)

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


# (n, l, k, d, block_k, lengths): the loop kernel's k chunks against the
# oracle. ``lengths`` None draws them in 1..k; "far" makes every set one
# slice far from V, so the e0 column wins every row.
CHUNK_CASES = {
    "k_not_multiple_of_kc": (150, 150, 7, 20, 3, None),
    "k_below_kc": (96, 40, 3, 33, 5, None),
    "lengths_end_mid_chunk": (130, 19, 8, 16, 4, (1, 2, 3, 5, 6, 7, 8)),
    "all_padding_chunk": (70, 12, 9, 24, 3, (1, 2, 3)),
    "singleton_e0_wins": (64, 9, 4, 12, 2, "far"),
}


def _chunk_problem(n, l, k, d, lengths, seed=8):
    V, S, lens, d_e0 = _problem(n, l, k, d, seed=seed)
    if lengths == "far":
        V = V - 2.0                          # near e0 = 0
        S = S + 10.0                         # every slice far from V
        lens = jnp.ones((l,), jnp.int32)
        d_e0 = jnp.sum(V ** 2, axis=1)
    elif lengths is not None:
        lens = jnp.asarray(np.resize(lengths, l).astype(np.int32))
    return V, S, lens, d_e0


def _padded_fused_loop(V, S, lengths, d_e0, policy, block_n, block_k):
    """``fused_eval``'s loop variant called directly: V, S and d_e0 padded
    to whole (Bn, Bl=128, kc) tiles by hand, k with zero slices."""
    from repro.kernels import exemplar_eval

    n, d = V.shape
    l, k, _ = S.shape
    pad = lambda x, shape: jnp.pad(x, [(0, t - s) for s, t in
                                       zip(x.shape, shape)])
    up = lambda x, m: -(-x // m) * m
    n_pad, l_pad, k_pad, d_pad = up(n, block_n), up(l, 128), up(k, block_k), 128
    Sk = jnp.transpose(S, (1, 0, 2)).astype(policy.compute_dtype)
    out = exemplar_eval.fused_eval(
        pad(V.astype(policy.compute_dtype), (n_pad, d_pad)),
        pad(Sk, (k_pad, l_pad, d_pad)),
        pad(lengths.astype(jnp.int32), (l_pad,))[:, None],
        pad(d_e0.astype(jnp.float32), (n_pad,))[:, None],
        n_total=n, policy=policy, block_n=block_n, block_l=128,
        block_k=block_k, variant="loop", interpret=True)
    return out[:l, 0]


@pytest.mark.parametrize("pname", ["fp32", "bf16"])
@pytest.mark.parametrize("case", list(CHUNK_CASES))
def test_loop_chunks_vs_oracle(case, pname):
    """k tiled in chunks of block_k across the grid: padded slices, sets
    that end inside a chunk or before it, and e0 winning, all match the
    oracle at the policy."""
    n, l, k, d, block_k, lengths = CHUNK_CASES[case]
    policy = POLICIES[pname]
    V, S, lens, d_e0 = _chunk_problem(n, l, k, d, lengths)
    want = ref.exemplar_eval_ref(V, S, lens, d_e0, policy=policy)
    got = _padded_fused_loop(V, S, lens, d_e0, policy, 64, block_k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    if lengths == "far":
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(jnp.mean(d_e0)), rtol=2e-5)


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


def _loop_working_set(cfgk, d_pad, itemsize):
    """VMEM bytes of a loop tile: V and the (kc, Bl, d) S chunk double
    buffered, kc f32 distance slices three times, the running min once."""
    bn, bl, kc = cfgk.block_n, cfgk.block_l, cfgk.block_k
    return (2 * (bn + bl * kc) * d_pad * itemsize
            + (3 * kc + 1) * bn * bl * 4)


def test_kernel_config_respects_budget():
    """The b_x/b_y analogue: the loop tile's S chunk and working set obey
    the VMEM budget (paper eq. for b_x) at any k."""
    from repro.kernels.ops import VMEM_S_BUDGET, kernel_config
    for k, d_pad in [(1, 128), (10, 128), (500, 128), (500, 256)]:
        cfgk = kernel_config(k, d_pad, FP32, l=100_000, n=100_000)
        assert cfgk.block_l * cfgk.block_k * d_pad * 4 <= VMEM_S_BUDGET
        assert _loop_working_set(cfgk, d_pad, 4) <= 3 * VMEM_S_BUDGET
        assert 1 <= cfgk.block_k <= k
        assert cfgk.block_l % 128 == 0 and cfgk.block_n % 8 == 0


@pytest.mark.parametrize("pname", ["fp32", "bf16"])
def test_loop_tile_lane_dense_at_paper_k500(pname):
    """At the paper's largest k the loop tile keeps a lane-dense set tile
    and tiles k in chunks that fit the working-set model; the two-pass
    tile, one slice at a time over the whole k, is what it was."""
    from repro.kernels.ops import VMEM_S_BUDGET, kernel_config
    policy = POLICIES[pname]
    cfgk = kernel_config(500, 128, policy, l=5_000, n=50_000,
                         variant="loop")
    assert cfgk.block_l >= 128 and cfgk.block_l % 128 == 0
    assert 1 < cfgk.block_k < 500
    assert _loop_working_set(cfgk, 128, policy.itemsize) <= 3 * VMEM_S_BUDGET
    two_pass = kernel_config(500, 128, policy, l=5_000, n=50_000,
                             variant="two_pass")
    assert (two_pass.block_n, two_pass.block_l, two_pass.block_k) == (
        1024, {"fp32": 16, "bf16": 32}[pname], 500)


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


def _gain_working_set(bn, bm, d_pad, itemsize):
    """VMEM bytes of a gain tile: V and C double buffered, and f32 (B, 1)
    columns at 128 lanes, ten of Bn rows and three of Bm."""
    return 2 * (bn + bm) * d_pad * itemsize + (10 * bn + 3 * bm) * 128 * 4


GAIN_SHAPES = [(50_000, 50_000, 128), (50_000, 5_000, 128),
               (1 << 20, 256, 128), (12_500, 50_000, 256),
               (50_000, 50_000, 1024), (300, 41, 128)]


@pytest.mark.parametrize("pname", ["fp32", "bf16"])
@pytest.mark.parametrize("shape", GAIN_SHAPES)
def test_gain_tile_respects_working_set(shape, pname):
    """The gain plan keeps its working set, (B, 1) blocks counted at 128
    lanes, under the same cap as the multiset plan."""
    from repro.kernels.ops import VMEM_S_BUDGET, gain_tile
    n, m, d_pad = shape
    policy = POLICIES[pname]
    bn, bm = gain_tile(n, m, d_pad, policy)
    assert _gain_working_set(bn, bm, d_pad, policy.itemsize) \
        <= 3 * VMEM_S_BUDGET
    assert bn % 8 == 0 and bm % 8 == 0


@pytest.mark.parametrize("pname", ["fp32", "bf16"])
def test_gain_tile_lane_dense_at_greedy_cell(pname):
    """At the Greedy cell's shape (n = m = 50,000, d = 100 in 128 lanes)
    both tile edges are whole lane rows, and the grid is smaller than the
    fixed 256×256 tile's 196 × 196 steps."""
    from repro.kernels.ops import gain_tile
    bn, bm = gain_tile(50_000, 50_000, 128, POLICIES[pname])
    assert bn % 128 == 0 and bm % 128 == 0
    assert -(-50_000 // bn) * -(-50_000 // bm) < 196 * 196


@pytest.mark.parametrize("n,m", [(1, 1), (7, 300), (300, 7), (133, 41),
                                 (257, 1000), (2000, 9)])
def test_gain_tile_clamps_small_problems(n, m):
    """A tile edge never passes the padded problem: at most
    _round_up(n, 8) rows and _round_up(m, 8) candidates, and the whole of
    either when it is smaller than the smallest tile."""
    from repro.kernels.ops import GAIN_TILES, _round_up, gain_tile
    bn, bm = gain_tile(n, m, 128, FP32)
    assert bn <= _round_up(n, 8) and bm <= _round_up(m, 8)
    if n < GAIN_TILES[0]:
        assert bn == _round_up(n, 8)
    if m < GAIN_TILES[0]:
        assert bm == _round_up(m, 8)
    bn_o, bm_o = ops._gain_blocks(n, m, 100, FP32, 4096, 4096)
    assert (bn_o, bm_o) == (_round_up(n, 8), _round_up(m, 8))


def test_gain_tile_picks_chip_fitted_tile():
    """At the Greedy cell's shape and fp32 the plan picks the tile the
    fitted chip costs (``ops.GAIN_COST_S``) put first."""
    from repro.kernels.ops import gain_tile
    assert gain_tile(50_000, 50_000, 128, FP32) == GREEDY_CELL_TILE


def test_grid_covers_problem():
    """Paper eq. 8: the grid tiles the whole work matrix."""
    from repro.kernels.ops import kernel_config, _round_up
    cfgk = kernel_config(10, 128, FP32, l=1000, n=50_000)
    l_pad = _round_up(1000, cfgk.block_l)
    n_pad = _round_up(50_000, cfgk.block_n)
    gl, gn = cfgk.grid(n_pad, l_pad)
    assert gl * cfgk.block_l >= 1000 and gn * cfgk.block_n >= 50_000
