"""jit'd wrappers composing the Pallas kernels into RedSync's selectors.

These mirror the pure-jnp selectors in core/selection.py (same Selected
contract) but route the hot loops through the TPU kernels:

    trimmed_topk           = abs_sum_max -> ratio loop(count_gt)
                             -> compact_gt -> exact top-k on the short bucket
    threshold_binary_search = abs_sum_max -> bisect loop(count_gt)
                             -> compact_gt -> first-2k filter

A lone leaf is a one-segment arena: the per-leaf kernels below are the
segmented kernels of ``kernels.segmented`` run over a single slot, so
the per-leaf and flat-arena pipelines share one kernel family.

``interpret`` defaults to None, resolved by
``segmented.resolve_interpret``: compiled kernels on a TPU backend,
interpreter mode on CPU (tests), an error anywhere else. The default is
what ``compressor_params["backend"] = "pallas"`` threads through the
compressor registry, so a TrainConfig needs no extra knob per platform.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.selection import (Selected, ladder_ratio, mean_of_sum,
                                  search_band, threshold_at)

from .segmented import (_cap_for, _gather_topk_from_buckets,
                        seg_abs_sum_max, seg_compact_gt, seg_count_gt,
                        seg_residual_update_stats)

DEFAULT_BLOCK = 1024


def _to2d(x: jax.Array, block: int) -> tuple[jax.Array, int]:
    n = x.size
    nb = max(1, -(-n // block))
    xp = jnp.pad(x.reshape(-1).astype(jnp.float32), (0, nb * block - n))
    return xp.reshape(nb, block), n


def _one_slot(nb: int) -> np.ndarray:
    return np.zeros(nb, np.int32)


def _bucket_cap(k: int, nb: int, block: int) -> int:
    """Bucket size for the k-of-2k selectors (trimmed / exact bsearch)."""
    return _cap_for(2 * k, nb, block)


def abs_sum_max(x2d: jax.Array, *, interpret: bool | None = None
                ) -> tuple[jax.Array, jax.Array]:
    """(sum|x|, max|x|) of a zero-padded [nb, block] leaf (Alg 2/3
    statistics; mean = sum / n is formed by the caller so padding
    contributes nothing)."""
    s, m = seg_abs_sum_max(x2d, _one_slot(x2d.shape[0]), 1,
                           interpret=interpret)
    return s[0], m[0]


def count_gt(x2d: jax.Array, threshold: jax.Array, *,
             interpret: bool | None = None) -> jax.Array:
    """nnz(|x| > t) as i32 — the count_nonzero loop of Alg 3. The
    threshold is an operand, so one compiled kernel serves every search
    iteration; t >= 0 drops the zero padding automatically."""
    thr = jnp.reshape(jnp.asarray(threshold, jnp.float32), (1,))
    return seg_count_gt(x2d, _one_slot(x2d.shape[0]), thr,
                        interpret=interpret)[0]


def compact_gt(x2d: jax.Array, threshold: jax.Array, cap_per_block: int,
               total: int, *, interpret: bool | None = None
               ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Block-bucketed compaction of a zero-padded [nb, block] leaf.
    Returns (values [nb, cap], indices [nb, cap] i32 — padding ==
    total, counts [nb])."""
    nb, block = x2d.shape
    thr = jnp.reshape(jnp.asarray(threshold, jnp.float32), (1,))
    return seg_compact_gt(x2d, _one_slot(nb),
                          np.arange(nb, dtype=np.int32) * block,
                          np.full(nb, total, np.int32), thr, cap_per_block,
                          interpret=interpret)


def trimmed_topk(x: jax.Array, k: int, *, eps: float = 0.2,
                 block: int = DEFAULT_BLOCK,
                 interpret: bool | None = None) -> Selected:
    """Algorithm 2 on the TPU kernels. capacity == k."""
    x2d, n = _to2d(x, block)
    nb = x2d.shape[0]
    s, mx = abs_sum_max(x2d, interpret=interpret)
    mean = mean_of_sum(s, n)

    def cond(state):
        step, nnz = state
        return jnp.logical_and(nnz < k, ladder_ratio(step, eps) > 0.0)

    def body(state):
        step, _ = state
        step = step + 1
        thr = threshold_at(mean, mx, ladder_ratio(step, eps))
        return step, count_gt(x2d, thr, interpret=interpret)

    step0 = jnp.int32(1)
    nnz0 = count_gt(x2d, threshold_at(mean, mx, ladder_ratio(step0, eps)),
                    interpret=interpret)
    step, nnz = jax.lax.while_loop(cond, body, (step0, nnz0))
    thr = threshold_at(mean, mx, ladder_ratio(step, eps))

    cap = _bucket_cap(k, nb, block)
    vals, idx, counts = compact_gt(x2d, thr, cap, n, interpret=interpret)
    si, sv = _gather_topk_from_buckets(vals, idx, k, n,
                                       order_by_magnitude=True)
    # The buckets cannot give the top-k when Alg 2's coarse (eps=0.2)
    # ladder bottoms out with fewer than k survivors, or leaves so many
    # that a block overflows its bucket and drops some: take the exact
    # selector for this (rare) iteration.
    fallback = (nnz < k) | jnp.any(counts > cap)

    def from_buckets(_):
        return si, sv

    def exact(_):
        from repro.core.selection import exact_topk
        s = exact_topk(x.reshape(-1).astype(jnp.float32), k)
        return s.indices, s.values

    si, sv = jax.lax.cond(fallback, exact, from_buckets, operand=None)
    return Selected(si, sv, jnp.int32(k))


def threshold_binary_search(x: jax.Array, k: int, *, eps: float = 1e-3,
                            warm: jax.Array | None = None,
                            block: int = DEFAULT_BLOCK,
                            interpret: bool | None = None
                            ) -> tuple[Selected, jax.Array]:
    """Algorithm 3 on the TPU kernels. capacity == 2k; returns threshold.

    ``warm`` seeds the bisection bracket from the previous converged
    threshold (``selection.search_band``); ``None`` is the cold search.
    """
    x2d, n = _to2d(x, block)
    s, mx = abs_sum_max(x2d, interpret=interpret)
    mean = mean_of_sum(s, n)
    thr = search_band(lambda t: count_gt(x2d, t, interpret=interpret),
                      mean, mx, k, eps, warm)
    return _filter_2d(x, x2d, n, thr, 2 * k, block,
                      interpret=interpret), thr


def threshold_filter(x: jax.Array, threshold: jax.Array, capacity: int, *,
                     block: int = DEFAULT_BLOCK,
                     interpret: bool | None = None) -> Selected:
    """First-``capacity`` |x| > threshold filter on the TPU kernels.

    Kernel twin of ``selection.threshold_filter`` (same overflow
    semantics, same count header) — the reuse branch of the bsearch
    compressor on the pallas backend, so threshold *reuse* steps skip the
    search kernels entirely instead of re-searching.
    """
    x2d, n = _to2d(x, block)
    return _filter_2d(x, x2d, n, threshold, capacity, block,
                      interpret=interpret)


def _filter_2d(x: jax.Array, x2d: jax.Array, n: int, thr: jax.Array,
               capacity: int, block: int, *, interpret: bool) -> Selected:
    """count -> compact -> first-``capacity`` gather, with the jnp filter
    as the bucket-overflow fallback."""
    nb = x2d.shape[0]
    nnz = count_gt(x2d, thr, interpret=interpret)
    cap = _cap_for(capacity, nb, block)
    vals, idx, counts = compact_gt(x2d, thr, cap, n, interpret=interpret)
    si, sv = _gather_topk_from_buckets(vals, idx, capacity, n,
                                       order_by_magnitude=False)
    # same overflow guard as trimmed_topk (search may exit on r-l <= eps
    # with nnz >> capacity); fall back to the jnp filter for exactness
    overflow = jnp.any(counts > cap)

    def from_buckets(_):
        return si, sv

    def exact(_):
        from repro.core.selection import threshold_filter as jnp_filter
        s = jnp_filter(x.reshape(-1).astype(jnp.float32), thr,
                       capacity=capacity)
        return s.indices, s.values

    si, sv = jax.lax.cond(overflow, exact, from_buckets, operand=None)
    return Selected(si, sv, jnp.minimum(nnz, capacity), nnz > capacity)


def residual_update(grad: jax.Array, u: jax.Array, v: jax.Array, *,
                    momentum: float, nesterov: bool,
                    block: int = DEFAULT_BLOCK,
                    interpret: bool | None = None
                    ) -> tuple[jax.Array, jax.Array]:
    """Fused U/V update on arbitrary-shaped leaves."""
    shape, n = grad.shape, grad.size
    g2, _ = _to2d(grad, block)
    u2, _ = _to2d(u, block)
    v2, _ = _to2d(v, block)
    v_new, u_new, _, _ = seg_residual_update_stats(
        g2, v2, u2, None, _one_slot(g2.shape[0]), 1, momentum=momentum,
        nesterov=nesterov, interpret=interpret)
    return (u_new.reshape(-1)[:n].reshape(shape),
            v_new.reshape(-1)[:n].reshape(shape))
