"""Pieces of the plain RGC reference against the definitions they
implement, on small arrays."""
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipref import rgc  # noqa: E402


@pytest.mark.parametrize("n,p,m", [(5000, 0.3, 700), (5000, 0.3, 5000),
                                   (3000, 0.01, 10), (1024, 0.5, 1),
                                   (10, 0.5, 3), (4096, 0.0, 5),
                                   (2500, 1.0, 1500)])
def test_first_by_index_is_the_running_count(n, p, m):
    a = np.random.default_rng(n + m).random(n) < p
    want = a & (np.cumsum(a) <= m)
    assert (np.asarray(rgc.first_by_index(jnp.asarray(a), m)) == want).all()


@pytest.mark.parametrize("k", [1, 7, 50])
def test_search_lands_in_band(k):
    ax = jnp.abs(jnp.asarray(np.random.default_rng(k).standard_normal(4000),
                             jnp.float32))
    thr = rgc.search(ax, k, jnp.float32(0.0), 1e-3)
    n = int(jnp.sum(ax > thr))
    assert k <= n <= 2 * k


def test_search_keeps_a_warm_threshold_in_band():
    ax = jnp.arange(1000, dtype=jnp.float32)
    # 10 entries above 989.5: in band for k = 8 (8 <= 10 <= 16)
    assert float(rgc.search(ax, 8, jnp.float32(989.5), 1e-3)) == 989.5


def test_leaf_k_rounds_up():
    assert rgc.leaf_k(15_000_000, 0.001) == 15_000
    assert rgc.leaf_k(6000, 0.001) == 6
    assert rgc.leaf_k(10, 0.001) == 1
