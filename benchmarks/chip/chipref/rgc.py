"""Plain reference of the cells' optimizer: residual gradient compression
with DGC momentum correction and local clipping, Alg 3 threshold
selection (RedSync §5.2.2; Lin et al. 1712.01887), data parallel over
``P`` workers.

One step on each worker ``w``, for every leaf:

1. clip the worker's whole gradient to norm ``clip_norm / sqrt(P)``;
2. ``u = momentum * u + g``; ``v = v + u``;
3. every ``interval`` steps (the first included) bisect for a threshold
   ``t`` with ``k <= #{|v| > t} <= 2k``, ``k = ceil(density * size)``,
   starting from the previous threshold; in between, reuse it;
4. send the first ``2k`` coordinates, by index, with ``|v| > t``, and
   clear ``u`` and ``v`` there.

Every worker then subtracts ``lr`` times the mean of all ``P`` messages
from its parameters.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from .numerics import Numerics


@dataclass(frozen=True)
class Settings:
    density: float
    lr: float
    momentum: float = 0.9
    clip_norm: float = 1.0
    eps: float = 1e-3
    interval: int = 5


def leaf_k(size: int, density: float) -> int:
    return max(1, int(math.ceil(density * size)))


def search(ax: jax.Array, k: int, warm: jax.Array, eps: float) -> jax.Array:
    """Threshold with k <= #{ax > t} <= 2k by bisection on the ratio
    coordinate of ``mean + ratio * (max - mean)``, bracketed by the
    previous threshold ``warm`` (which is kept if already in band)."""
    mean = jnp.sum(ax) / ax.size
    span = jnp.max(ax) - mean

    def count(t):
        return jnp.sum(ax > t)

    def in_band(n):
        return (n >= k) & (n <= 2 * k)

    nnz0 = count(warm)
    r_prev = jnp.where(span > 0, (warm - mean) / jnp.maximum(span, 1e-30), 0.0)
    r_prev = jnp.clip(r_prev, 0.0, 1.0)
    l0 = jnp.where(nnz0 > 2 * k, r_prev, 0.0)
    r0 = jnp.where(nnz0 < k, r_prev, 1.0)

    def cond(c):
        l, r, n = c
        return ~in_band(n) & (r - l > eps)

    def body(c):
        l, r, _ = c
        ratio = l + 0.5 * (r - l)
        n = count(mean + ratio * span)
        return jnp.where(n > 2 * k, ratio, l), jnp.where(n < k, ratio, r), n

    l, r, _ = jax.lax.while_loop(cond, body, (l0, r0, nnz0))
    return jnp.where(in_band(nnz0), warm, mean + (l + 0.5 * (r - l)) * span)


def first_by_index(above: jax.Array, m: int, block: int = 1024) -> jax.Array:
    """``above`` with only its first ``m`` true entries, by index, kept.

    Counts per block of ``block`` entries locate the block that holds the
    ``m``-th true entry; only that block needs a running count."""
    n = above.size
    nb = -(-n // block)
    a = jnp.pad(above, (0, nb * block - n)).reshape(nb, block)
    counts = jnp.sum(a, axis=1, dtype=jnp.int32)
    before = jnp.cumsum(counts) - counts
    last = jnp.clip(jnp.sum(before < m) - 1, 0, nb - 1)
    ranks = jnp.cumsum(a[last].astype(jnp.int32))
    in_last = a[last] & (before[last] + ranks <= m)
    rows = jnp.arange(nb)[:, None]
    keep = (a & (rows < last)) | ((rows == last) & in_last[None, :])
    return keep.reshape(-1)[:n]


def init_worker(params, nx: Numerics) -> dict:
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, nx.dtype), params)
    return {"u": zeros, "v": zeros,
            "thr": jax.tree.map(lambda p: jnp.float32(0.0), params),
            "interval": jnp.int32(0)}


@partial(jax.jit, static_argnames=("st", "n_workers", "dtype"))
def worker_update(grads, state, *, st: Settings, n_workers: int, dtype):
    """Steps 1-4 on one worker: (message tree, new state)."""
    sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
             for g in jax.tree.leaves(grads))
    limit = st.clip_norm / math.sqrt(n_workers)
    scale = jnp.minimum(1.0, limit / jnp.maximum(jnp.sqrt(sq), 1e-12))
    refresh = state["interval"] % st.interval == 0

    def leaf(g, u, v, thr):
        g = (g.astype(jnp.float32) * scale).astype(dtype)
        u = (st.momentum * u + g).astype(dtype)
        v = (v + u).astype(dtype)
        ax = jnp.abs(v.astype(jnp.float32)).reshape(-1)
        k = leaf_k(ax.size, st.density)
        thr = jax.lax.cond(refresh, lambda: search(ax, k, thr, st.eps),
                           lambda: thr)
        keep = first_by_index(ax > thr, 2 * k).reshape(v.shape)
        zero = jnp.zeros_like(v)
        return (jnp.where(keep, v, zero).astype(jnp.float32),
                jnp.where(keep, zero, u), jnp.where(keep, zero, v), thr)

    out = jax.tree.map(leaf, grads, state["u"], state["v"], state["thr"])
    def pick(i):
        return jax.tree.map(lambda o: o[i], out,
                            is_leaf=lambda x: isinstance(x, tuple))

    return pick(0), {"u": pick(1), "v": pick(2), "thr": pick(3),
                     "interval": state["interval"] + 1}


@jax.jit
def apply(params, msg_sum, lr, n_workers):
    return jax.tree.map(
        lambda p, m: (p.astype(jnp.float32) - lr * (m / n_workers))
        .astype(p.dtype), params, msg_sum)


@jax.jit
def tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)
