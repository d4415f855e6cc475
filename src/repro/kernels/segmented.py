"""Segmented Pallas kernels + selectors for the flat residual arenas.

One arena coalesces many same-dtype leaves (``repro.core.arena``); these
kernels run each pipeline stage ONCE over the whole arena while keeping
selection *segmented* — every slot keeps its own ``k_i``, statistics,
threshold and bucket capacity, so the communicated set is bitwise
identical to running the per-leaf selectors leaf by leaf:

* ``seg_abs_sum_max``   — per-segment (sum|x|, max|x|) in one pass (the
                          per-leaf ``block_stats`` twin);
* ``seg_count_gt``      — per-segment nnz(|x| > t_i) with a PER-SEGMENT
                          threshold vector (one launch per search step
                          for the whole arena instead of per leaf);
* ``seg_compact_gt``    — ``compact.compact_gt`` extended to per-segment
                          thresholds and slot-local indices: block-
                          bucketed compaction of every slot's survivors
                          in one launch (the trimmed selector's);
* ``seg_residual_update_stats`` — the fused hot loop: momentum-corrected
                          residual accumulation (Alg 4 l.11-19) AND the
                          Alg 2/3 block statistics of the updated
                          residual in a single pass over the arena (one
                          HBM round-trip instead of two).

``seg_first_gt`` (plain jnp, both backends) is the bsearch selector's
compaction: every bsearch slot's first ``cap`` survivors in slot order,
exactly, by prefix sums and bisections, with no bucket and no scatter.

Bitwise parity rests on the arena layout: slots are ``ARENA_BLOCK``-
aligned and zero-padded, so each slot's rows are exactly the 2-D view a
lone leaf has, and each segment's sums add its rows in ascending order
wherever the slot sits (see "Tiling" below). The per-leaf selectors in
``ops`` run these same kernels over one segment.

The ``*_segments`` selectors orchestrate the kernels into Algorithm 2/3
over all slots at once: threshold search loops are vectorized across
segments with converged segments FROZEN (their state stops updating), so
every segment walks the exact iterate sequence its per-leaf loop would.
``use_pallas=False`` routes through the pure-jnp twins in ``ref.py`` —
the same math the per-leaf jnp selectors in ``core.selection`` run.

``interpret`` follows ``resolve_interpret`` (None = by backend).
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.selection import (Selected, bisect_midpoint, ladder_ratio,
                                  threshold_at, warm_ratio)

from . import ref

__all__ = [
    "seg_abs_sum_max", "seg_count_gt", "seg_compact_gt",
    "seg_residual_update_stats", "seg_stats", "seg_mean",
    "seg_counts", "seg_first_gt", "SegmentSpec", "multi_select",
    "trimmed_topk_segments", "threshold_bsearch_segments",
]


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------
#
# Tiling. Each kernel walks the [nb, block] arena ``_rows(nb)`` rows at a
# time: ROW_BLOCK rows (a multiple of 16, so f32 and bf16 blocks are
# legal Mosaic tiles), or the whole arena when it is shorter. Per-segment
# inputs and accumulators are (1, n_seg) vectors, so nothing is read or
# written as a VMEM scalar and no per-row metadata array is streamed in.
#
# A row block may straddle slot boundaries (slots are aligned to one row,
# not to a row block). Each kernel recovers the owning segment of every
# row from the per-segment row bounds [lo, hi): rows past ``nb`` in the
# last, partial block belong to no segment and contribute nothing.
# Per-segment sums add one row sum at a time in ascending row order — the
# same chain whichever rows share a block — so a slot's statistics do not
# depend on where it sits in the arena, and the per-leaf, per-arena and
# stacked-arena launches agree bitwise.

ROW_BLOCK = 64


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> compiled on TPU, interpreted on CPU (tests). The
    kernels have no lowering for any other backend, so that raises
    instead of silently interpreting."""
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"the Pallas selection kernels target TPU (interpreted on "
            f"CPU); backend {backend!r} has neither — use backend='jnp'")
    return backend == "cpu"


def _rows(nb: int) -> int:
    return nb if nb <= ROW_BLOCK else ROW_BLOCK


def _bounds(block_seg, n_seg: int) -> tuple[np.ndarray, np.ndarray]:
    """Per-segment [lo, hi) row bounds of an ascending ``block_seg``."""
    bs = np.asarray(block_seg)
    if np.any(np.diff(bs) < 0):
        raise ValueError("block_seg must be ascending (contiguous slots)")
    ids = np.arange(n_seg)
    return (np.searchsorted(bs, ids, "left").astype(np.int32),
            np.searchsorted(bs, ids, "right").astype(np.int32))


def _seg_strides(stride_b, lo: np.ndarray) -> np.ndarray:
    st = np.asarray(stride_b, np.int32)[lo]
    if np.any(st < 1) or np.any(st & (st - 1)):
        raise ValueError(f"sampling strides must be powers of two: {st}")
    return st


def _vec(a, dtype=jnp.int32) -> jax.Array:
    a = jnp.asarray(a, dtype)
    return a.reshape(1, a.size)


def _vec_spec(n_seg: int) -> pl.BlockSpec:
    return pl.BlockSpec((1, n_seg), lambda i: (0, 0))


def _row_spec(rows: int, width: int) -> pl.BlockSpec:
    return pl.BlockSpec((rows, width), lambda i: (i, 0))


def _row_ids(rows: int) -> jax.Array:
    return (pl.program_id(0) * rows
            + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0))


def _hit(lo_ref, hi_ref, rows: int) -> jax.Array:
    """(rows, n_seg): row r of this block belongs to segment s."""
    g = _row_ids(rows)
    return (lo_ref[...] <= g) & (g < hi_ref[...])


def _per_row(hit: jax.Array, vec_ref) -> jax.Array:
    """Each row's entry of a per-segment vector, as a (rows, 1) column
    (exact: one nonzero term; 0 for rows of no segment)."""
    v = vec_ref[...]
    return jnp.sum(jnp.where(hit, v, jnp.zeros((), v.dtype)), axis=1,
                   keepdims=True)


def _on_stride(hit: jax.Array, stride_ref, shape) -> jax.Array:
    """Columns on each row's power-of-two sampling grid (strides divide
    the block, so these are the slot-local ``[::stride]`` elements)."""
    st = jnp.maximum(_per_row(hit, stride_ref), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (col & (st - 1)) == 0


def _accumulate_stats(lo_ref, hi_ref, sum_ref, max_ref, ax: jax.Array):
    """Add this block's per-segment (sum, max) of ``ax`` into the
    (1, n_seg) accumulators, summing row by row in ascending order."""
    rows = ax.shape[0]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        sum_ref[...] = jnp.zeros(sum_ref.shape, sum_ref.dtype)
        max_ref[...] = jnp.zeros(max_ref.shape, max_ref.dtype)

    rs = jnp.sum(ax, axis=1, keepdims=True)
    rm = jnp.max(ax, axis=1, keepdims=True)
    lo, hi = lo_ref[...], hi_ref[...]
    g0 = pl.program_id(0) * rows
    acc = sum_ref[...]
    for r in range(rows):
        on = (lo <= g0 + r) & (g0 + r < hi)
        acc = acc + jnp.where(on, rs[r:r + 1], 0.0)
    sum_ref[...] = acc
    hit = _hit(lo_ref, hi_ref, rows)
    max_ref[...] = jnp.maximum(
        max_ref[...],
        jnp.max(jnp.where(hit, rm, 0.0), axis=0, keepdims=True))


def _stats_kernel(lo_ref, hi_ref, *refs, strided: bool):
    if strided:
        stride_ref, x_ref, sum_ref, max_ref = refs
    else:
        x_ref, sum_ref, max_ref = refs
    ax = jnp.abs(x_ref[...].astype(jnp.float32))
    if strided:
        hit = _hit(lo_ref, hi_ref, ax.shape[0])
        ax = jnp.where(_on_stride(hit, stride_ref, ax.shape), ax, 0.0)
    _accumulate_stats(lo_ref, hi_ref, sum_ref, max_ref, ax)


def seg_abs_sum_max(x2d: jax.Array, block_seg: np.ndarray, n_seg: int, *,
                    stride_b: np.ndarray | None = None,
                    interpret: bool | None = None
                    ) -> tuple[jax.Array, jax.Array]:
    """Per-segment (sum|x|, max|x|) over [nb, block] arena rows.

    ``stride_b`` (per-row power-of-two ints) restricts the statistics to
    each row's stride grid for the sampled selector; ``None`` keeps the
    exact-path kernel.
    """
    nb, block = x2d.shape
    lo, hi = _bounds(block_seg, n_seg)
    rows = _rows(nb)
    vec = _vec_spec(n_seg)
    ins, in_specs = [_vec(lo), _vec(hi)], [vec, vec]
    if stride_b is not None:
        ins.append(_vec(_seg_strides(stride_b, lo)))
        in_specs.append(vec)
    ins.append(x2d)
    in_specs.append(_row_spec(rows, block))
    s, m = pl.pallas_call(
        functools.partial(_stats_kernel, strided=stride_b is not None),
        grid=(pl.cdiv(nb, rows),),
        in_specs=in_specs,
        out_specs=[vec, vec],
        out_shape=[jax.ShapeDtypeStruct((1, n_seg), jnp.float32)] * 2,
        interpret=resolve_interpret(interpret),
    )(*ins)
    return s[0], m[0]


def _count_kernel(lo_ref, hi_ref, *refs, strided: bool):
    if strided:
        stride_ref, thr_ref, x_ref, out_ref = refs
    else:
        thr_ref, x_ref, out_ref = refs

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    ax = jnp.abs(x_ref[...].astype(jnp.float32))
    hit = _hit(lo_ref, hi_ref, ax.shape[0])
    over = ax > _per_row(hit, thr_ref)
    if strided:
        over = over & _on_stride(hit, stride_ref, ax.shape)
    c = jnp.sum(over.astype(jnp.int32), axis=1, keepdims=True)
    out_ref[...] += jnp.sum(jnp.where(hit, c, 0), axis=0, keepdims=True)


def seg_count_gt(x2d: jax.Array, block_seg: np.ndarray,
                 thresholds: jax.Array, *,
                 stride_b: np.ndarray | None = None,
                 interpret: bool | None = None
                 ) -> jax.Array:
    """Per-segment nnz(|x| > thresholds[seg]) — one launch per search
    step for the whole arena.

    ``stride_b`` counts only each row's stride-grid columns (the sampled
    selector's subsample count — integer, so stride-1 rows are exact)."""
    nb, block = x2d.shape
    n_seg = thresholds.shape[0]
    lo, hi = _bounds(block_seg, n_seg)
    rows = _rows(nb)
    vec = _vec_spec(n_seg)
    ins, in_specs = [_vec(lo), _vec(hi)], [vec, vec]
    if stride_b is not None:
        ins.append(_vec(_seg_strides(stride_b, lo)))
        in_specs.append(vec)
    ins += [_vec(thresholds, jnp.float32), x2d]
    in_specs += [vec, _row_spec(rows, block)]
    out = pl.pallas_call(
        functools.partial(_count_kernel, strided=stride_b is not None),
        grid=(pl.cdiv(nb, rows),),
        in_specs=in_specs,
        out_specs=vec,
        out_shape=jax.ShapeDtypeStruct((1, n_seg), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(*ins)
    return out[0]


def _lane_cumsum(m: jax.Array) -> jax.Array:
    """Inclusive prefix sum along lanes (log-step shifted adds; Mosaic
    has no cumsum lowering)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, m.shape, 1)
    s = 1
    while s < m.shape[1]:
        m = m + jnp.where(lane >= s, pltpu.roll(m, s, 1), 0)
        s *= 2
    return m


def _compact_kernel(lo_ref, hi_ref, size_ref, thr_ref, x_ref,
                    vals_ref, idx_ref, cnt_ref, slot_scr, base_scr,
                    size_scr, *, cap: int):
    rows, block = x_ref.shape
    x = x_ref[...].astype(jnp.float32)
    hit = _hit(lo_ref, hi_ref, rows)
    base = (_row_ids(rows) - _per_row(hit, lo_ref)) * block
    size = _per_row(hit, size_ref)
    lidx = base + jax.lax.broadcasted_iota(jnp.int32, (rows, block), 1)
    mask = (jnp.abs(x) > _per_row(hit, thr_ref)) & (lidx < size)
    m = mask.astype(jnp.int32)
    cnt_ref[...] = jnp.sum(m, axis=1, keepdims=True)
    # bucket slot of each survivor (0-based); overflow beyond cap -> -1
    pos = _lane_cumsum(m) - 1
    slot_scr[...] = jnp.where(mask & (pos < cap), pos, -1)
    base_scr[...] = base
    size_scr[...] = size

    # Per row, a [cap, block] one-hot packs survivors into bucket slots
    # with exact masked lane sums (one nonzero term each), and a [cap,
    # cap] diagonal turns the resulting column into the output row.
    slot_ids = jax.lax.broadcasted_iota(jnp.int32, (cap, block), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (cap, cap), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (cap, cap), 1))
    col = jax.lax.broadcasted_iota(jnp.int32, (1, cap), 1)

    def pack_row(r, carry):
        at = pl.ds(r, 1)
        onehot = slot_scr[at, :] == slot_ids
        xr = x_ref[at, :].astype(jnp.float32)
        v = jnp.sum(jnp.where(onehot, xr, 0.0), axis=1, keepdims=True)
        o = jnp.sum(jnp.where(onehot, lane, 0), axis=1, keepdims=True)
        v = jnp.sum(jnp.where(eye, v, 0.0), axis=0, keepdims=True)
        o = jnp.sum(jnp.where(eye, o, 0), axis=0, keepdims=True)
        vals_ref[at, :] = v
        idx_ref[at, :] = jnp.where(col < cnt_ref[at, :],
                                   base_scr[at, :] + o, size_scr[at, :])
        return carry

    jax.lax.fori_loop(0, rows, pack_row, 0)


def seg_compact_gt(x2d: jax.Array, block_seg: np.ndarray,
                   block_base: np.ndarray, block_size: np.ndarray,
                   thresholds: jax.Array, cap_per_block: int, *,
                   interpret: bool | None = None
                   ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Block-bucketed compaction with per-segment thresholds and
    SLOT-LOCAL indices.

    GPU RedSync compacts survivors (|x| > t) with a device-wide prefix
    sum and scattered writes; here every arena row packs its own
    survivors to the front of a private ``cap_per_block`` bucket — no
    cross-row carry. Returns (values [nb, cap], indices [nb, cap] i32 —
    local to the owning slot, padding == slot size, counts [nb]
    pre-clamp, so the caller detects bucket overflow). Indices are
    packed as exact i32 sums: f32 cannot hold indices past 2^24.
    ``block_base`` must be each row's offset within its slot (the arena
    layout) and ``block_size`` the owning slot's size.
    """
    nb, block = x2d.shape
    n_seg = thresholds.shape[0]
    lo, hi = _bounds(block_seg, n_seg)
    seg = np.asarray(block_seg)
    if not np.array_equal(np.asarray(block_base),
                          (np.arange(nb) - lo[seg]) * block):
        raise ValueError("block_base must be each row's offset in its slot")
    size = np.asarray(block_size, np.int32)[lo]
    rows = _rows(nb)
    vec = _vec_spec(n_seg)
    cap = cap_per_block
    vals, idx, cnt = pl.pallas_call(
        functools.partial(_compact_kernel, cap=cap),
        grid=(pl.cdiv(nb, rows),),
        in_specs=[vec, vec, vec, vec, _row_spec(rows, block)],
        out_specs=[_row_spec(rows, cap), _row_spec(rows, cap),
                   _row_spec(rows, 1)],
        out_shape=[
            jax.ShapeDtypeStruct((nb, cap), jnp.float32),
            jax.ShapeDtypeStruct((nb, cap), jnp.int32),
            jax.ShapeDtypeStruct((nb, 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((rows, block), jnp.int32),
                        pltpu.VMEM((rows, 1), jnp.int32),
                        pltpu.VMEM((rows, 1), jnp.int32)],
        interpret=resolve_interpret(interpret),
    )(_vec(lo), _vec(hi), _vec(size), _vec(thresholds, jnp.float32), x2d)
    return vals, idx, cnt[:, 0]


def _resid_kernel(*refs, momentum: float, nesterov: bool,
                  weight_decay: float, round_dtype, has_u: bool,
                  has_p: bool):
    it = iter(refs)
    lo_ref, hi_ref, g_ref, v_ref = (next(it) for _ in range(4))
    u_ref = next(it) if has_u else None
    p_ref = next(it) if has_p else None
    v_out = next(it)
    u_out = next(it) if has_u else None
    sum_ref, max_ref = next(it), next(it)

    g = g_ref[...].astype(jnp.float32)
    if has_p:
        g = g + weight_decay * p_ref[...].astype(jnp.float32)
    v = v_ref[...]
    if has_u:
        u = momentum * u_ref[...] + g
        v_new = v + u
        if nesterov:
            v_new = v_new + g
        u_out[...] = u
    else:
        v_new = v + g
    if round_dtype is not None:
        v_new = v_new.astype(round_dtype).astype(jnp.float32)
    v_out[...] = v_new
    _accumulate_stats(lo_ref, hi_ref, sum_ref, max_ref, jnp.abs(v_new))


def seg_residual_update_stats(
    g2d: jax.Array,
    v2d: jax.Array,
    u2d: jax.Array | None,
    p2d: jax.Array | None,
    block_seg: np.ndarray,
    n_seg: int,
    *,
    momentum: float,
    nesterov: bool,
    weight_decay: float = 0.0,
    round_dtype=None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array | None, jax.Array, jax.Array]:
    """Fused Alg 4 accumulation + Alg 2/3 statistics in ONE arena pass.

    Returns (V' [nb, block], U' or None, per-seg sum|V'|, per-seg
    max|V'|). The velocity update ``U' = momentum * U + g`` runs iff
    ``u2d`` is given (required when ``momentum`` is nonzero); ``p2d``
    is required iff ``weight_decay`` is nonzero. ``round_dtype`` rounds
    V' through the residual storage dtype (bf16 residuals) before
    statistics, matching the per-leaf store-then-reload sequence.
    """
    nb, block = g2d.shape
    if momentum and u2d is None:
        raise ValueError("momentum accumulation needs the velocity arena")
    if weight_decay and p2d is None:
        raise ValueError("weight decay needs the parameter arena")
    has_u, has_p = u2d is not None, bool(weight_decay)
    lo, hi = _bounds(block_seg, n_seg)
    rows = _rows(nb)
    row = _row_spec(rows, block)
    vec = _vec_spec(n_seg)

    ins = [_vec(lo), _vec(hi), g2d, v2d]
    in_specs = [vec, vec, row, row]
    if has_u:
        ins.append(u2d)
        in_specs.append(row)
    if has_p:
        ins.append(p2d)
        in_specs.append(row)
    planes = 1 + has_u
    out_shape = ([jax.ShapeDtypeStruct((nb, block), jnp.float32)] * planes
                 + [jax.ShapeDtypeStruct((1, n_seg), jnp.float32)] * 2)
    kern = functools.partial(
        _resid_kernel, momentum=momentum, nesterov=nesterov,
        weight_decay=weight_decay, round_dtype=round_dtype, has_u=has_u,
        has_p=has_p)
    outs = pl.pallas_call(
        kern, grid=(pl.cdiv(nb, rows),), in_specs=in_specs,
        out_specs=[row] * planes + [vec, vec],
        out_shape=out_shape, interpret=resolve_interpret(interpret),
    )(*ins)
    v_new = outs[0]
    u_new = outs[1] if has_u else None
    sums, maxs = outs[-2:]
    return v_new, u_new, sums[0], maxs[0]


# ---------------------------------------------------------------------------
# Segmented selectors (Algorithm 2/3 across all slots at once)
# ---------------------------------------------------------------------------

def _cap_for(capacity: int, nb: int, block: int) -> int:
    """Per-row bucket size for gathering ``capacity`` survivors: 4x the
    uniform per-row share, rounded to the 8-sublane granule, clamped to
    the block."""
    per = -(-capacity // nb)
    return min(block, max(8, ((4 * per + 7) // 8) * 8))


def _gather_topk_from_buckets(vals, idx, k: int, total: int,
                              order_by_magnitude: bool):
    """Pick k entries from the [nb, cap] buckets: by |value| (trimmed top-k)
    or simply the first-k valid slots (binary-search filter)."""
    fv, fi = vals.reshape(-1), idx.reshape(-1)
    valid = fi < total
    if order_by_magnitude:
        score = jnp.where(valid, jnp.abs(fv), -1.0)
    else:
        score = valid.astype(jnp.float32)
    _, pos = jax.lax.top_k(score, k)
    sel_idx = jnp.where(valid[pos], fi[pos], total)
    sel_val = jnp.where(valid[pos], fv[pos], 0.0)
    return sel_idx.astype(jnp.int32), sel_val


def seg_mean(sums: jax.Array, geom, stride_seg=None) -> jax.Array:
    """Per-segment mean from per-segment sums — the pinned reciprocal
    multiply of ``selection.mean_of_sum``, vectorized over slots. The
    ONE definition both ``seg_stats`` and the fused accumulate+stats
    path use, so their statistics can never diverge. ``stride_seg``
    divides by each slot's SAMPLED element count instead (the sampled
    selector's subsample mean)."""
    from repro.core.residual import pinned_product
    if stride_seg is None:
        ns = geom.seg_sizes
    else:
        ns = [-(-n // int(s)) for n, s in zip(geom.seg_sizes, stride_seg)]
    recip = jnp.asarray([jnp.float32(1.0 / n) for n in ns])
    return pinned_product(sums, recip)


def seg_stats(x2d: jax.Array, geom, *, use_pallas: bool,
              interpret: bool | None = None, stride_seg=None
              ) -> tuple[jax.Array, jax.Array]:
    """Per-segment (mean|x|, max|x|). The jnp twin reduces each slot's
    own [nblocks, block] rows with the shapes ``selection._stats`` uses,
    so per-leaf statistics are reproduced bitwise on either backend.
    ``stride_seg`` computes subsample statistics for the sampled paths
    (``None`` / all-ones keeps the exact kernels untouched)."""
    strided = stride_seg is not None and any(int(s) > 1 for s in stride_seg)
    if not strided:
        stride_seg = None
    if use_pallas:
        stride_b = None if stride_seg is None else \
            np.asarray(stride_seg, np.int32)[np.asarray(geom.block_seg)]
        sums, maxs = seg_abs_sum_max(x2d, geom.block_seg, geom.n_seg,
                                     stride_b=stride_b, interpret=interpret)
    else:
        sums, maxs = ref.seg_abs_sum_max(x2d, geom.block_seg,
                                         geom.block_size, geom.n_seg,
                                         stride_seg)
    return seg_mean(sums, geom, stride_seg), maxs


def seg_counts(x2d: jax.Array, geom, thresholds: jax.Array, *,
               use_pallas: bool, interpret: bool | None = None,
               stride_b=None) -> jax.Array:
    if use_pallas:
        return seg_count_gt(x2d, geom.block_seg, thresholds,
                            stride_b=stride_b, interpret=interpret)
    return ref.seg_count_gt(x2d, geom.block_seg, thresholds, geom.n_seg,
                            stride_b)


def _seg_buckets(x2d, geom, thresholds, cap, *, use_pallas, interpret):
    if use_pallas:
        return seg_compact_gt(x2d, geom.block_seg, geom.block_base,
                              geom.block_size, thresholds, cap,
                              interpret=interpret)
    return ref.seg_compact_gt(x2d, geom.block_seg, geom.block_base,
                              geom.block_size, thresholds, cap)


def _lower_bound(fetch, target: jax.Array, lo, hi, steps: int
                 ) -> jax.Array:
    """Per query, the least ``i`` in ``[lo, hi]`` with ``fetch(i) >=
    target``, for a non-decreasing table that reaches ``target`` at
    ``hi``: ``steps`` halvings of one gather each (``steps`` must cover
    the widest range, ``(hi - lo).bit_length()``). Results stay in
    ``[lo, hi]`` whatever the targets, so every gather is in bounds."""
    for _ in range(steps):
        mid = (lo + hi) // 2
        go = fetch(mid) >= target
        hi = jnp.where(go, mid, hi)
        lo = jnp.where(go, lo, mid + 1)
    return lo


def seg_first_gt(x2d: jax.Array, geom, thresholds: jax.Array,
                 capacities, segments) -> dict[int, Selected]:
    """Each listed segment's first ``capacities[s]`` elements with
    ``|x| > thresholds[s]``, in slot order, padded with the slot size:
    bitwise ``selection.threshold_filter`` of the slot, for all of them
    in one exact, order-preserving compaction with no scatter.

    The survivor mask (lanes past a slot's size left out) gives each
    row's inclusive lane prefix ``pos`` and, from its last lane, the
    row's count; a prefix of those counts ranks every row's survivors
    across the arena. An output's wanted rank is then found by two
    vectorized bisections — its row over the row prefix, within the
    segment's rows, then its lane over that row's ``pos`` — and one
    gather takes the value. Returns ``{segment: Selected}`` with
    ``count = min(nnz, cap)`` and ``overflow = nnz > cap``.
    """
    block = x2d.shape[1]
    x = x2d.astype(jnp.float32)
    segs = [int(s) for s in segments]
    on = np.zeros(geom.n_seg, bool)
    on[segs] = True
    # other segments' rows get an infinite threshold: no survivors
    thr = jnp.where(jnp.asarray(on), jnp.asarray(thresholds, jnp.float32),
                    jnp.inf)
    thr_b = thr[jnp.asarray(np.asarray(geom.block_seg), jnp.int32)]
    lidx = (jnp.asarray(np.asarray(geom.block_base), jnp.int32)[:, None]
            + jnp.arange(block, dtype=jnp.int32)[None, :])
    size_b = jnp.asarray(np.asarray(geom.block_size), jnp.int32)[:, None]
    mask = (jnp.abs(x) > thr_b[:, None]) & (lidx < size_b)
    # a row holds at most ``block`` survivors: int16 halves the one
    # arena-sized integer array
    pos = jnp.cumsum(mask, axis=1, dtype=jnp.int16)
    cnt = pos[:, -1].astype(jnp.int32)
    incl = jnp.cumsum(cnt)                      # survivors up to each row
    excl = incl - cnt                           # survivors before it

    # per output: the wanted arena-wide rank (1-based) and its row range
    caps = [int(capacities[s]) for s in segs]
    rows = [geom.seg_rows[s] for s in segs]
    nnz = {s: incl[r1 - 1] - excl[r0] for s, (r0, r1) in zip(segs, rows)}
    want, lo, hi = [], [], []
    for s, c, (r0, r1) in zip(segs, caps, rows):
        j = jnp.minimum(jnp.arange(c, dtype=jnp.int32),
                        jnp.maximum(nnz[s] - 1, 0))
        want.append(excl[r0] + j + 1)
        lo.append(jnp.full((c,), r0, jnp.int32))
        hi.append(jnp.full((c,), r1 - 1, jnp.int32))
    want = jnp.concatenate(want)
    row_steps = max((r1 - 1 - r0).bit_length() for r0, r1 in rows)
    row = _lower_bound(lambda i: incl[i], want, jnp.concatenate(lo),
                       jnp.concatenate(hi), row_steps)
    col = _lower_bound(lambda c: pos[row, c],
                       (want - excl[row]).astype(jnp.int16),
                       jnp.zeros_like(row), jnp.full_like(row, block - 1),
                       (block - 1).bit_length())
    val = x[row, col]

    out: dict[int, Selected] = {}
    off = 0
    for s, c, (r0, _r1) in zip(segs, caps, rows):
        size = geom.seg_sizes[s]
        ok = jnp.arange(c, dtype=jnp.int32) < nnz[s]
        span = slice(off, off + c)
        out[s] = Selected(
            jnp.where(ok, (row[span] - r0) * block + col[span], size),
            jnp.where(ok, val[span], 0.0),
            jnp.minimum(nnz[s], c), nnz[s] > c)
        off += c
    return out


def _slot_flat(x2d: jax.Array, geom, s: int) -> jax.Array:
    """Slot ``s`` as the flat f32[size] vector the per-leaf path sees."""
    r0, r1 = geom.seg_rows[s]
    return x2d[r0:r1].reshape(-1)[:geom.seg_sizes[s]]


class SegmentSpec(NamedTuple):
    """One arena's selection request for ``multi_select``.

    ``alg`` picks the search (Alg 2 ratio ladder vs Alg 3 bisection);
    the runtime fields drive §5.2.2 threshold reuse (``refresh`` /
    ``cached``), warm-started bisection (``warm``) and DGC-style sampled
    counting (``strides`` — per-slot subsample strides; all-1 is exact).
    ``capacities`` are per-slot message capacities (defaulting to ``k``
    for trimmed and ``2k`` for bsearch when empty).
    """
    alg: str                              # "trimmed" | "bsearch"
    eps: float
    capacities: tuple[int, ...] = ()
    strides: tuple[int, ...] = ()
    refresh: jax.Array | None = None      # bool[n_seg]
    cached: jax.Array | None = None       # f32[n_seg]
    warm: bool = False


def _norm_caps(spec: SegmentSpec, geom) -> tuple[int, ...]:
    if spec.capacities:
        return tuple(spec.capacities)
    if spec.alg == "trimmed":
        return tuple(geom.seg_ks)
    return tuple(2 * k for k in geom.seg_ks)


def _norm_strides(spec: SegmentSpec, geom) -> tuple[int, ...]:
    if spec.strides and spec.alg == "bsearch":
        return tuple(int(s) for s in spec.strides)
    return (1,) * geom.n_seg


def multi_select(
    parts: list[tuple[jax.Array, Any, SegmentSpec,
                      tuple[jax.Array, jax.Array] | None]],
    *,
    use_pallas: bool,
    interpret: bool | None = None,
) -> list[tuple[list[Selected], jax.Array]]:
    """Algorithm 2 AND 3 across every slot of every arena in ONE dispatch
    per search iteration.

    ``parts`` is ``[(x2d, geometry, SegmentSpec, stats-or-None), ...]``
    — one entry per arena. The arenas are row-stacked into a virtual
    super-arena (``arena.stack_geometries``) and both threshold walks run
    in a single unified ``while_loop``: trimmed segments step their
    pinned ratio ladder, bsearch segments bisect their bracket, and every
    iteration issues ONE ``seg_count_gt`` launch for all segments of all
    arenas. Converged (or reuse / warm-accepted) segments are FROZEN —
    their carried state stops updating — so each segment still walks
    exactly the iterate sequence its per-leaf selector would, and the
    selected sets stay bitwise identical to the per-leaf path.

    Compaction follows the search, once for all arenas. Every bsearch
    segment's message is its slot's ``threshold_filter`` at the final
    threshold — the first ``cap`` survivors in slot order — and
    ``seg_first_gt`` computes it exactly for all of them in one pass
    (scope ``compact``), on either backend. Trimmed segments go through
    one ``seg_compact_gt`` bucket launch and a top-k by magnitude, with
    a per-slot exact ``fallback`` branch where a bucket overflowed.

    Returns one ``(selections, thresholds)`` pair per part, in order.
    """
    geoms = [p[1] for p in parts]
    specs = [p[2] for p in parts]
    if len(parts) == 1:
        x_all, geom_all = parts[0][0], geoms[0]
    else:
        from repro.core.arena import stack_geometries
        x_all = jnp.concatenate([p[0] for p in parts], axis=0)
        geom_all = stack_geometries(geoms)

    n = geom_all.n_seg
    k_vec = jnp.asarray(geom_all.seg_ks, jnp.int32)
    two_k = 2 * k_vec

    # --- static per-segment vectors -------------------------------------
    trim_np = np.concatenate([
        np.full(g.n_seg, s.alg == "trimmed") for g, s in zip(geoms, specs)])
    eps_np = np.concatenate([
        np.full(g.n_seg, s.eps, np.float32) for g, s in zip(geoms, specs)])
    warm_np = np.concatenate([
        np.full(g.n_seg, bool(s.warm) and s.alg == "bsearch")
        for g, s in zip(geoms, specs)])
    strides = sum((_norm_strides(s, g) for g, s in zip(geoms, specs)), ())
    caps_sel = sum((_norm_caps(s, g) for g, s in zip(geoms, specs)), ())
    is_trim = jnp.asarray(trim_np)
    eps_vec = jnp.asarray(eps_np)
    warm_vec = jnp.asarray(warm_np)
    any_trim = bool(trim_np.any())
    any_warm = bool(warm_np.any())
    sampled = any(s > 1 for s in strides)
    stride_b = np.asarray(strides, np.int64)[
        np.asarray(geom_all.block_seg)].astype(np.int32) if sampled else None
    stride_vec = jnp.asarray(strides, jnp.int32)

    # --- runtime per-segment vectors ------------------------------------
    refresh = jnp.concatenate([
        jnp.asarray(s.refresh) if s.refresh is not None
        else jnp.ones((g.n_seg,), bool) for g, s in zip(geoms, specs)])
    have_cached = any(s.cached is not None for s in specs)
    cached = jnp.concatenate([
        jnp.asarray(s.cached, jnp.float32) if s.cached is not None
        else jnp.zeros((g.n_seg,), jnp.float32)
        for g, s in zip(geoms, specs)])

    # --- statistics (per-segment — independent of arena grouping) -------
    if all(p[3] is None for p in parts):
        mean, mx = seg_stats(x_all, geom_all, use_pallas=use_pallas,
                             interpret=interpret,
                             stride_seg=strides if sampled else None)
    else:
        means, maxs = [], []
        for (x2d, geom, spec, stats) in parts:
            if stats is None:
                st = _norm_strides(spec, geom)
                stats = seg_stats(
                    x2d, geom, use_pallas=use_pallas, interpret=interpret,
                    stride_seg=st if any(s > 1 for s in st) else None)
            means.append(stats[0])
            maxs.append(stats[1])
        mean, mx = jnp.concatenate(means), jnp.concatenate(maxs)

    def count_est(thr):
        """One launch: per-segment survivor counts; sampled segments
        count their subsample and scale by the stride (integer — exact
        segments are untouched by the scaling)."""
        cnt = seg_counts(x_all, geom_all, thr, use_pallas=use_pallas,
                         interpret=interpret, stride_b=stride_b)
        return cnt * stride_vec if sampled else cnt

    def in_band(nz):
        return (nz >= k_vec) & (nz <= two_k)

    # --- initial probe: trimmed rung 1 + warm cached thresholds ---------
    step0 = jnp.ones((n,), jnp.int32)
    if any_trim or any_warm:
        thr0 = jnp.where(is_trim,
                         threshold_at(mean, mx, ladder_ratio(step0, eps_vec)),
                         cached)
        cnt0 = count_est(thr0)
        accept = warm_vec & refresh & ~is_trim & in_band(cnt0)
        use0 = is_trim | warm_vec
        nnz0 = jnp.where(use0, cnt0, jnp.int32(-1))
        r_prev = warm_ratio(cached, mean, mx)
        seed = warm_vec & ~is_trim
        l0 = jnp.where(seed & (cnt0 > two_k), r_prev,
                       jnp.zeros((n,), jnp.float32))
        r0 = jnp.where(seed & (cnt0 < k_vec), r_prev,
                       jnp.ones((n,), jnp.float32))
    else:
        accept = jnp.zeros((n,), bool)
        nnz0 = jnp.full((n,), -1, jnp.int32)
        l0 = jnp.zeros((n,), jnp.float32)
        r0 = jnp.ones((n,), jnp.float32)

    # --- unified search loop: one count launch per iteration ------------
    def trim_active(step, nnz):
        return is_trim & (nnz < k_vec) & (ladder_ratio(step, eps_vec) > 0.0)

    def bs_active(l, r, nnz):
        return (~is_trim & refresh & ~accept & ~in_band(nnz)
                & ((r - l) > eps_vec))

    def cond(state):
        step, l, r, nnz = state
        return jnp.any(trim_active(step, nnz) | bs_active(l, r, nnz))

    def body(state):
        step, l, r, nnz = state
        ta = trim_active(step, nnz)
        ba = bs_active(l, r, nnz)
        step = jnp.where(ta, step + 1, step)
        ratio_b = bisect_midpoint(l, r)
        ratio = jnp.where(is_trim, ladder_ratio(step, eps_vec), ratio_b)
        with jax.named_scope("search"):
            cnt = count_est(threshold_at(mean, mx, ratio))
        nnz = jnp.where(ta | ba, cnt, nnz)
        r = jnp.where(ba & (cnt < k_vec), ratio_b, r)
        l = jnp.where(ba & (cnt > two_k), ratio_b, l)
        return step, l, r, nnz

    step, l, r, nnz_loop = jax.lax.while_loop(
        cond, body, (step0, l0, r0, nnz0))

    ratio_fin = jnp.where(is_trim, ladder_ratio(step, eps_vec),
                          bisect_midpoint(l, r))
    thr = threshold_at(mean, mx, ratio_fin)
    if any_warm:
        thr = jnp.where(accept, cached, thr)
    if have_cached:
        thr = jnp.where(is_trim | refresh, thr, cached)

    # --- compaction: bsearch slots exactly, trimmed slots in buckets ----
    bs_segs = np.flatnonzero(~trim_np)
    if bs_segs.size:
        with jax.named_scope("compact"):
            first = seg_first_gt(x_all, geom_all, thr, caps_sel, bs_segs)
    if any_trim:
        caps = {s: _cap_for(2 * geom_all.seg_ks[s], r1 - r0, geom_all.block)
                for s, (r0, r1) in enumerate(geom_all.seg_rows)
                if trim_np[s]}
        vals, idx, cnts = _seg_buckets(x_all, geom_all, thr,
                                       max(caps.values()),
                                       use_pallas=use_pallas,
                                       interpret=interpret)

    # --- per-slot results (trimmed: plain jnp on the short buckets) -----
    results: list[tuple[list[Selected], jax.Array]] = []
    seg0 = 0
    for (x2d, geom, spec, _stats) in parts:
        out: list[Selected] = []
        for sl, (k, size) in enumerate(zip(geom.seg_ks, geom.seg_sizes)):
            s = seg0 + sl
            if spec.alg != "trimmed":
                out.append(first[s])
                continue
            row0, row1 = geom_all.seg_rows[s]
            cap = caps[s]
            si, sv = _gather_topk_from_buckets(
                vals[row0:row1, :cap], idx[row0:row1, :cap], k, size,
                order_by_magnitude=True)
            # too few survivors (the ladder bottomed out) or a dropped
            # one (bucket overflow): the buckets cannot yield the top-k
            fallback = jnp.any(cnts[row0:row1] > cap) | (nnz_loop[s] < k)
            if use_pallas:
                # mirror ops.trimmed_topk: exact top-k
                def exact(_, sl=sl, k=k, x2d=x2d, geom=geom):
                    from repro.core.selection import exact_topk
                    with jax.named_scope("fallback"):
                        e = exact_topk(_slot_flat(x2d, geom, sl), k)
                    return e.indices, e.values
            else:
                # mirror selection.trimmed_topk (no buckets at all): the
                # full top-k pads with real zero-score indices when
                # nnz < k
                def exact(_, sl=sl, k=k, t=thr[s], x2d=x2d, geom=geom):
                    from repro.core.selection import _pad_topk
                    with jax.named_scope("fallback"):
                        flat = _slot_flat(x2d, geom, sl)
                        score = jnp.where(jnp.abs(flat) > t,
                                          jnp.abs(flat), 0.0)
                        e = _pad_topk(flat, score, k)
                    return e.indices, e.values

            si, sv = jax.lax.cond(fallback, exact,
                                  lambda _, si=si, sv=sv: (si, sv),
                                  operand=None)
            out.append(Selected(si, sv, jnp.int32(k)))
        results.append((out, thr[seg0:seg0 + geom.n_seg]))
        seg0 += geom.n_seg
    return results


def trimmed_topk_segments(
    x2d: jax.Array,
    geom,
    *,
    eps: float = 0.2,
    use_pallas: bool,
    interpret: bool | None = None,
    stats: tuple[jax.Array, jax.Array] | None = None,
) -> list[Selected]:
    """Algorithm 2 over every slot of one arena (capacity == k_i each).

    Single-arena wrapper over ``multi_select`` (the ratio walk runs
    vectorized with converged segments frozen, so each slot's final
    threshold is bitwise the per-leaf loop's).
    """
    spec = SegmentSpec(alg="trimmed", eps=eps)
    ((sel, _thr),) = multi_select([(x2d, geom, spec, stats)],
                                  use_pallas=use_pallas, interpret=interpret)
    return sel


def threshold_bsearch_segments(
    x2d: jax.Array,
    geom,
    *,
    eps: float = 1e-3,
    use_pallas: bool,
    interpret: bool | None = None,
    stats: tuple[jax.Array, jax.Array] | None = None,
    refresh: jax.Array | None = None,
    cached: jax.Array | None = None,
    warm: bool = False,
    strides: tuple[int, ...] = (),
    capacities: tuple[int, ...] = (),
) -> tuple[list[Selected], jax.Array]:
    """Algorithm 3 over every slot of one arena (capacity == 2 k_i each
    unless ``capacities`` overrides, e.g. the sampled selector's
    tolerance headroom).

    ``refresh``/``cached`` implement §5.2.2 threshold reuse (segments
    with ``refresh[s] == False`` skip the bisect entirely and filter at
    ``cached[s]``); ``warm`` seeds refreshing segments' brackets from
    ``cached``; ``strides`` turns on sampled counting. Single-arena
    wrapper over ``multi_select``. Returns the per-slot selections and
    the per-segment thresholds used (the new ``LeafState.threshold``
    cache).
    """
    spec = SegmentSpec(alg="bsearch", eps=eps, capacities=capacities,
                       strides=strides, refresh=refresh, cached=cached,
                       warm=warm)
    ((sel, thr),) = multi_select([(x2d, geom, spec, stats)],
                                 use_pallas=use_pallas, interpret=interpret)
    return sel, thr
