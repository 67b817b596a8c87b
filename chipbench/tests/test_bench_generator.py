"""The traffic generator: a pure function of the seed (all 64 bits of it)
and the block index, ids inside the catalogue, and the same ranks whichever
search the inverse CDF takes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import generator
from chipbench.scenarios import stationary

CONFIG = {"n_objects": 5000, "alpha": 1.1}


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 7, 2**32 - 1])
def test_key_is_prngkey_of_the_seed(seed):
    np.testing.assert_array_equal(generator.key_data(seed), np.asarray(jax.random.PRNGKey(seed)))


def test_seeds_past_32_bits_differ():
    assert not np.array_equal(generator.key_data(2**40 + 1), generator.key_data(1))


@pytest.mark.parametrize("traffic", [
    {"scenario": "stationary", "chunk_len": 512},
    {"scenario": "stationary", "n_samples": 3, "sample_len": 400},
])
def test_blocks_are_a_function_of_seed_and_index(traffic):
    a = generator.Traffic(CONFIG, traffic, 2**33 + 5)
    b = generator.Traffic(CONFIG, traffic, 2**33 + 5)
    c = generator.Traffic(CONFIG, traffic, 2**33 + 6)
    x0, x1 = np.asarray(a.block(0)), np.asarray(a.block(1))
    assert x0.shape == a.shape and x0.dtype == np.int32
    assert 0 <= x0.min() and x0.max() < CONFIG["n_objects"]
    np.testing.assert_array_equal(x0, np.asarray(b.block(0)))
    assert not np.array_equal(x0, x1)
    assert not np.array_equal(x0, np.asarray(c.block(0)))


def test_sort_and_scan_searches_give_the_same_ranks():
    cdf = jnp.asarray(generator.zipf_cdf(1000, 1.1))
    u = jax.random.uniform(jax.random.PRNGKey(3), (4000,))  # more draws than ranks: sort
    want = jnp.minimum(jnp.searchsorted(cdf, u, side="right", method="scan"), 999)
    np.testing.assert_array_equal(stationary.ranks(cdf, u, 1000), want)
    np.testing.assert_array_equal(stationary.ranks(cdf, u[:10], 1000), want[:10])


def test_ranks_follow_zipf():
    t = generator.Traffic(CONFIG, {"scenario": "stationary", "chunk_len": 20000}, 11)
    ids = np.asarray(t.block(0))
    p0 = 1 / np.sum(np.arange(1, 5001) ** -1.1)
    assert abs((ids == 0).mean() - p0) < 0.02 and (ids < 50).mean() > 0.5
