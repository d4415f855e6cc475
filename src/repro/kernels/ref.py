"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth the kernels are validated against
(tests/test_kernels.py sweeps shapes/dtypes and asserts allclose).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def abs_sum_max(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(sum(|x|), max(|x|)) — the statistics feeding Alg 2/3 thresholds."""
    ax = jnp.abs(x.astype(jnp.float32))
    return jnp.sum(ax), jnp.max(ax)


def count_gt(x: jax.Array, threshold: jax.Array) -> jax.Array:
    """nnz(|x| > threshold) as i32 — the count_nonzero hot loop of Alg 3."""
    return jnp.sum(jnp.abs(x.astype(jnp.float32)) > threshold).astype(jnp.int32)


def compact_gt(
    x: jax.Array, threshold: jax.Array, block: int, cap_per_block: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Block-bucketed stream compaction oracle.

    Splits flat ``x`` into ``block``-sized blocks; within each block emits the
    first ``cap_per_block`` elements with |x| > threshold (padded with index
    == x.size, value 0) plus the per-block survivor count (pre-clamp).

    Returns (values [nb, cap], indices [nb, cap] i32, counts [nb] i32).
    """
    n = x.size
    nb = -(-n // block)
    xp = jnp.pad(x.astype(jnp.float32).reshape(-1), (0, nb * block - n))
    xb = xp.reshape(nb, block)
    gidx = jnp.arange(nb * block).reshape(nb, block)
    mask = (jnp.abs(xb) > threshold) & (gidx < n)

    def per_block(xrow, mrow, grow):
        (pos,) = jnp.nonzero(mrow, size=cap_per_block, fill_value=block)
        safe = jnp.minimum(pos, block - 1)
        vals = jnp.where(pos < block, xrow[safe], 0.0)
        idxs = jnp.where(pos < block, grow[safe], n)
        return vals, idxs.astype(jnp.int32), jnp.sum(mrow).astype(jnp.int32)

    return jax.vmap(per_block)(xb, mask, gidx)


def residual_update(
    grad: jax.Array,
    u: jax.Array,
    v: jax.Array,
    *,
    momentum: float,
    nesterov: bool,
) -> tuple[jax.Array, jax.Array]:
    """Fused momentum-correction + residual accumulation (Alg 4 l.11–19)."""
    g = grad.astype(jnp.float32)
    u_new = momentum * u + g
    v_new = v + u_new
    if nesterov:
        v_new = v_new + g
    return u_new, v_new


# ---------------------------------------------------------------------------
# Segmented twins (the flat-arena kernels of kernels/segmented.py)
# ---------------------------------------------------------------------------

def _seg_rows(block_seg) -> list[tuple[int, int]]:
    """Contiguous [row0, row1) row range per segment ordinal."""
    bs = np.asarray(block_seg)
    starts = np.searchsorted(bs, np.arange(bs.max() + 1), side="left")
    ends = np.searchsorted(bs, np.arange(bs.max() + 1), side="right")
    return [(int(s), int(e)) for s, e in zip(starts, ends)]


def seg_abs_sum_max(x2d: jax.Array, block_seg, block_size,
                    n_seg: int, stride_seg=None
                    ) -> tuple[jax.Array, jax.Array]:
    """Per-segment (sum|x|, max|x|) over the arena's [nb, block] rows.

    Each segment's sum runs ``selection.pinned_sum`` over the slot's
    TRUE-length flat vector (padding sliced off) — the exact pinned
    summation tree ``selection._stats`` runs for that leaf on its own,
    so the per-segment mean is bitwise the per-leaf mean in any graph
    context. ``block_size`` carries the owning slot's true size per row.

    ``stride_seg`` (per-segment ints) restricts the statistics to the
    slot's ``[::stride]`` subsample — the same vector the sampled
    per-leaf selector slices, so sampled per-leaf and sampled segmented
    statistics stay bitwise too. ``None`` / stride 1 is the exact path.
    """
    from repro.core.selection import pinned_sum
    ax = jnp.abs(x2d.astype(jnp.float32))
    bsize = np.asarray(block_size)
    sums, maxs = [], []
    for s, (r0, r1) in enumerate(_seg_rows(block_seg)):
        seg = ax[r0:r1]
        stride = 1 if stride_seg is None else int(stride_seg[s])
        if stride > 1:
            vec = seg.reshape(-1)[:int(bsize[r0]):stride]
            sums.append(pinned_sum(vec))
            maxs.append(jnp.max(vec))
        else:
            sums.append(pinned_sum(seg.reshape(-1)[:int(bsize[r0])]))
            maxs.append(jnp.max(seg))
    return jnp.stack(sums), jnp.stack(maxs)


def seg_count_gt(x2d: jax.Array, block_seg, thresholds: jax.Array,
                 n_seg: int, stride_b=None) -> jax.Array:
    """Per-segment nnz(|x| > thresholds[seg]) (integer — order-free).

    ``stride_b`` (per-row ints) counts only columns on the row's stride
    grid — the sampled paths' subsample count. Strides divide the block
    and slots are block-aligned, so ``col % stride == 0`` is exactly the
    slot-local ``[::stride]`` grid the per-leaf sampled count scans.
    """
    seg = jnp.asarray(np.asarray(block_seg), jnp.int32)
    thr_b = jnp.asarray(thresholds, jnp.float32)[seg]
    mask = jnp.abs(x2d.astype(jnp.float32)) > thr_b[:, None]
    if stride_b is not None:
        col = jnp.arange(x2d.shape[1], dtype=jnp.int32)[None, :]
        sb = jnp.asarray(np.asarray(stride_b), jnp.int32)[:, None]
        mask = mask & (col % sb == 0)
    cnt_b = jnp.sum(mask, axis=1).astype(jnp.int32)
    return jax.ops.segment_sum(cnt_b, seg, num_segments=n_seg)


def seg_compact_gt(x2d: jax.Array, block_seg, block_base, block_size,
                   thresholds: jax.Array, cap_per_block: int
                   ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Block-bucketed compaction with per-segment thresholds.

    Twin of ``segmented.seg_compact_gt``: per arena row, the first
    ``cap_per_block`` elements with |x| > thr of the owning segment are
    packed to the front; indices are slot-LOCAL with padding == the
    slot's size; counts are pre-clamp survivor counts.
    """
    nb, block = x2d.shape
    x = x2d.astype(jnp.float32)
    seg = jnp.asarray(np.asarray(block_seg), jnp.int32)
    base = jnp.asarray(np.asarray(block_base), jnp.int32)
    size = jnp.asarray(np.asarray(block_size), jnp.int32)
    thr_b = jnp.asarray(thresholds, jnp.float32)[seg]

    lidx = base[:, None] + jnp.arange(block, dtype=jnp.int32)[None, :]
    mask = (jnp.abs(x) > thr_b[:, None]) & (lidx < size[:, None])
    cnts = jnp.sum(mask, axis=1).astype(jnp.int32)

    cap = cap_per_block
    pos = jnp.cumsum(mask, axis=1) - 1
    live = mask & (pos < cap)
    row = jnp.arange(nb)[:, None]
    # scatter survivors into [nb, cap] buckets (+1 dump slot for the rest)
    tgt = jnp.where(live, row * cap + pos, nb * cap).reshape(-1)
    vals = jnp.zeros(nb * cap + 1, jnp.float32) \
        .at[tgt].set(x.reshape(-1))[:nb * cap].reshape(nb, cap)
    sentinel = jnp.broadcast_to(size[:, None], (nb, cap)).reshape(-1)
    idx = jnp.concatenate([sentinel, jnp.zeros(1, jnp.int32)]) \
        .at[tgt].set(lidx.reshape(-1))[:nb * cap].reshape(nb, cap)
    return vals, idx.astype(jnp.int32), cnts


def seg_residual_update_stats(
    g2d: jax.Array,
    v2d: jax.Array,
    u2d: jax.Array | None,
    p2d: jax.Array | None,
    block_seg,
    n_seg: int,
    *,
    momentum: float,
    nesterov: bool,
    weight_decay: float = 0.0,
    round_dtype=None,
) -> tuple[jax.Array, jax.Array | None, jax.Array, jax.Array]:
    """Twin of the fused arena accumulate+stats pass (Alg 4 + Alg 2/3)."""
    g = g2d.astype(jnp.float32)
    if weight_decay:
        g = g + weight_decay * p2d.astype(jnp.float32)
    if u2d is not None:
        u_new, v_new = residual_update(g, u2d, v2d, momentum=momentum,
                                       nesterov=nesterov)
    else:
        u_new, v_new = None, v2d + g
    if round_dtype is not None:
        v_new = v_new.astype(round_dtype).astype(jnp.float32)
    sums, maxs = _plain_seg_abs_sum_max(v_new, block_seg, n_seg)
    return v_new, u_new, sums, maxs


def _plain_seg_abs_sum_max(x2d, block_seg, n_seg):
    """Sequential-blockwise per-segment stats (the fused-kernel oracle:
    the Pallas grid accumulates block sums in ascending row order)."""
    ax = jnp.abs(x2d.astype(jnp.float32))
    sums, maxs = [], []
    for r0, r1 in _seg_rows(block_seg):
        seg = ax[r0:r1]
        sums.append(jnp.sum(jnp.sum(seg, axis=1)))
        maxs.append(jnp.max(seg))
    return jnp.stack(sums), jnp.stack(maxs)
