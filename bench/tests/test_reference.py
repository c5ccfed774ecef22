"""The plain references against the program's own oracles, on the CPU at
tiny sizes. The references import nothing of the program; this test does,
to see that both compute the same thing."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import datagen
from bench.reference import multiset as ref_multiset


@pytest.mark.parametrize("seed", [0, 1, 2**33 + 1])
def test_multiset_matches_kernel_oracle(seed):
    from repro.core.precision import FP32
    from repro.kernels.ref import exemplar_eval_ref

    V = datagen.uniform(datagen.seed_key(seed), n=300, d=20, low=0.0,
                        high=1.0)
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, 300, size=(24, 6))
    lengths = rng.integers(1, 7, size=24)
    got = ref_multiset.multiset_values(V, idx, lengths, block_sets=8)
    want = exemplar_eval_ref(V, V[jnp.asarray(idx)], jnp.asarray(lengths),
                             jnp.sum(V * V, axis=1), FP32)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-6)


def test_blocks_by_size_give_the_same_values():
    V = datagen.uniform(datagen.seed_key(4), n=200, d=16, low=0.0, high=1.0)
    idx = np.random.default_rng(4).integers(0, 200, size=(30, 7))
    lengths = np.full(30, 7)
    whole = ref_multiset.multiset_values(V, idx, lengths)
    np.testing.assert_array_equal(
        ref_multiset.multiset_values(V, idx, lengths, block_sets=4), whole)


def test_high_is_a_step_below_highest():
    V = datagen.uniform(datagen.seed_key(7), n=512, d=100, low=0.0,
                        high=1.0)
    idx = np.arange(0, 512, 7)[None, :]
    lengths = np.array([idx.shape[1]])
    exact = ref_multiset.multiset_values(V, idx, lengths, "highest")[0]
    high = ref_multiset.multiset_values(V, idx, lengths, "high")[0]
    assert 1e-6 < abs(high - exact) / exact < 1e-3
