"""Communication-set selection (RedSync §5.2, Algorithms 2/3/5).

All selectors operate on a flat f32 residual vector and return a
fixed-capacity sparse message ``Selected(indices, values, count)``:

* ``exact_topk``       — radixSelect stand-in (``jax.lax.top_k``); the paper's
                         baseline selector. capacity == k.
* ``trimmed_topk``     — Alg 2: statistics-guided threshold trimming, then an
                         exact top-k restricted to survivors. capacity == k.
* ``threshold_binary_search`` — Alg 3: binary-search a threshold t with
                         k <= nnz(|x|>t) <= 2k; no exact top-k at all.
                         capacity == 2k, padded; true length in ``count``.

Quantized variants (§5.2.3) select by *signed value* (top-k one iteration,
bottom-k the next — the ``phase`` argument) so the communication set is
same-signed and a single scalar mean represents all values.

JAX constraint: shapes are static, so capacity is fixed at trace time. Padding
uses index == size (out of range); decompression drops padded entries via the
``count`` header, mirroring the paper's ``(len, idx, val)`` packed message.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class Selected(NamedTuple):
    """Fixed-capacity sparse communication set.

    ``overflow`` is only populated by the threshold-filter selectors
    (whose survivor count is data-dependent); the top-k selectors always
    produce exactly ``k`` survivors and leave it ``None``. Within any one
    selector path the field is consistently an array or consistently
    ``None`` so ``lax.cond`` branches keep matching pytree structures.
    """
    indices: jax.Array   # i32[cap], padded entries == x.size
    values: jax.Array    # f32[cap] (zeros at padding)
    count: jax.Array     # i32[] true number of selected elements (<= cap)
    overflow: jax.Array | None = None  # bool[] nnz exceeded capacity


# Slot alignment granule of the flat residual arenas. Matches the Pallas
# kernels' VMEM block (kernels.ops.DEFAULT_BLOCK) so that a slot's padded
# 2-D view inside an arena is bit-for-bit the view the per-leaf kernels
# build for that leaf on its own.
STATS_BLOCK = 1024


def pinned_sum(v: jax.Array) -> jax.Array:
    """Sum with a PINNED floating-point summation tree (pairwise halving).

    ``jnp.sum``'s partial-sum order is an XLA implementation detail — the
    CPU backend may split one reduce into reduce-window chunks (or not)
    depending on the surrounding fusion, so the same vector can sum to
    last-ulp-different totals in differently-shaped graphs. That breaks
    the flat-arena refactor's bitwise guarantee through the Alg 2/3 mean.
    This sum zero-pads to a power of two and halves with ELEMENTWISE adds
    — elementwise ops have no reduction order for XLA to choose, so the
    addition tree is identical in every graph context.
    """
    flat = v.reshape(-1)
    size = 1 << max(0, int(flat.size - 1).bit_length())
    flat = jnp.pad(flat, (0, size - flat.size))
    while flat.size > 1:
        half = flat.size // 2
        flat = flat[:half] + flat[half:]
    return flat[0]


def mean_of_sum(total: jax.Array, n) -> jax.Array:
    """``total / n`` as a pinned multiply by the f32 reciprocal.

    A literal division by a constant may be strength-reduced to a
    reciprocal multiply under fast math in one graph shape and left as a
    true division in another — a last-ulp lottery, like the FMA
    contraction ``pinned_product`` guards against. Precomputing the f32
    reciprocal in Python and pinning the multiply makes the mean a fixed
    function of ``total`` everywhere. (``n < 2**24`` loses nothing; the
    mean is a selection heuristic, not an accumulator.)

    ``n`` may also be a runtime array (the quantized mean divides by a
    data-dependent count): the reciprocal is then a standalone division
    — never fused, so still a fixed function of its inputs — followed by
    the same pinned multiply.
    """
    from .residual import pinned_product
    if isinstance(n, (int, float)):
        return pinned_product(total, jnp.float32(1.0 / n))
    return pinned_product(total, jnp.float32(1.0) / n.astype(jnp.float32))


def _stats(ax: jax.Array) -> tuple[jax.Array, jax.Array]:
    """mean and max of a non-negative vector (|x|), order-pinned.

    The mean's summation tree is pinned (``pinned_sum``) and the /n is a
    pinned reciprocal multiply (``mean_of_sum``) so per-leaf and
    segmented-arena selection see bitwise-identical statistics; max is
    order-insensitive and stays a plain reduce.
    """
    return mean_of_sum(pinned_sum(ax), ax.size), jnp.max(ax)


def threshold_at(mean: jax.Array, mx: jax.Array,
                 ratio: jax.Array) -> jax.Array:
    """The Alg 2/3 candidate threshold ``mean + ratio * (mx - mean)``.

    The product is contraction-pinned (``residual.pinned_product``): XLA
    would otherwise FMA-contract it in some graph shapes and not others,
    and a last-ulp threshold difference between the per-leaf and
    flat-arena pipelines eventually flips a boundary element of the
    communication set. Shared by the jnp selectors here, the per-leaf
    Pallas wrappers (kernels.ops) and the segmented-arena selectors
    (kernels.segmented) — one definition, bitwise everywhere.
    """
    from .residual import pinned_product
    return mean + pinned_product(ratio, mx - mean)


def bisect_midpoint(l: jax.Array, r: jax.Array) -> jax.Array:
    """``l + (r - l) / 2`` with the halving contraction-pinned.

    XLA strength-reduces the ``/ 2.0`` to ``* 0.5`` (value-identical)
    and may then FMA-contract it with the ``l +`` — graph-shape
    dependent, like ``threshold_at``'s product. Same pin, same reason.
    """
    from .residual import pinned_product
    return l + pinned_product(jnp.float32(0.5), r - l)


def ladder_ratio(step: jax.Array, eps) -> jax.Array:
    """Alg 2 ratio after ``step`` rungs: ``1 - step * eps``, pinned.

    The naive ladder (``ratio -= eps`` in the loop carry) accumulates f32
    decrement error — five steps of 0.2 land at 4.5e-8, not 0.0, which
    admits a spurious near-zero extra iteration. Recomputing each rung
    from the integer step count with one pinned product makes the rung
    values exact at representable boundaries and — more importantly —
    identical between the scalar per-leaf loops and the vectorized
    segmented loops at every step.

    ``step`` is i32 (scalar or per-segment vector); ``eps`` a float or
    f32 vector.
    """
    from .residual import pinned_product
    eps = jnp.asarray(eps, jnp.float32)
    return jnp.float32(1.0) - pinned_product(step.astype(jnp.float32), eps)


def warm_ratio(thr: jax.Array, mean: jax.Array, mx: jax.Array) -> jax.Array:
    """A previous threshold's ratio coordinate under the *current* stats.

    Inverse of ``threshold_at``, clipped into the ``[0, 1]`` search
    interval; degenerate spans (``mx <= mean``) map to 0 so a warm start
    on them degrades to the cold bracket. The reciprocal is a standalone
    division and the multiply is contraction-pinned, keeping the scalar
    per-leaf and vectorized segmented versions elementwise identical.
    """
    from .residual import pinned_product
    span = mx - mean
    safe = jnp.maximum(span, jnp.float32(1e-30))
    r = pinned_product(thr - mean, jnp.float32(1.0) / safe)
    return jnp.clip(jnp.where(span > 0, r, jnp.float32(0.0)), 0.0, 1.0)


def _pad_topk(x: jax.Array, score: jax.Array, k: int) -> Selected:
    """Exact top-k by ``score``; values taken from ``x``."""
    _, idx = jax.lax.top_k(score, k)
    return Selected(idx.astype(jnp.int32), x[idx], jnp.int32(k))


# ---------------------------------------------------------------------------
# Baseline: exact top-k (the "radixSelect" reference point)
# ---------------------------------------------------------------------------

def exact_topk(x: jax.Array, k: int) -> Selected:
    return _pad_topk(x, jnp.abs(x), k)


# ---------------------------------------------------------------------------
# Algorithm 2: trimmed top-k
# ---------------------------------------------------------------------------

def trimmed_topk(x: jax.Array, k: int, eps: float = 0.2) -> Selected:
    """Find a threshold that keeps >=k survivors, then top-k the survivors.

    Survivor restriction is expressed by zeroing the score of trimmed
    elements; on TPU the survivor set is first compacted into a small buffer
    by the Pallas block-bucketed compaction kernel (kernels/segmented.py),
    which is where the paper's speedup comes from. The selected set is
    identical.
    """
    ax = jnp.abs(x)
    mean, mx = _stats(ax)

    def cond(state):
        step, nnz = state
        return jnp.logical_and(nnz < k, ladder_ratio(step, eps) > 0.0)

    def body(state):
        step, _ = state
        step = step + 1
        thr = threshold_at(mean, mx, ladder_ratio(step, eps))
        return step, jnp.sum(ax > thr)

    step0 = jnp.int32(1)
    nnz0 = jnp.sum(ax > threshold_at(mean, mx, ladder_ratio(step0, eps)))
    step, _ = jax.lax.while_loop(cond, body, (step0, nnz0))
    thr = threshold_at(mean, mx, ladder_ratio(step, eps))
    trimmed_score = jnp.where(ax > thr, ax, 0.0)
    return _pad_topk(x, trimmed_score, k)


# ---------------------------------------------------------------------------
# Algorithm 3: threshold binary search selection
# ---------------------------------------------------------------------------

def search_band(count_at, mean: jax.Array, mx: jax.Array, k: int,
                eps: float, warm: jax.Array | None = None) -> jax.Array:
    """The Alg 3 bisection: a threshold t with ``k <= count_at(t) <= 2k``.

    ``count_at`` maps a threshold to an i32 survivor count — a full scan
    for the exact selectors, a strided-subsample count (scaled back up)
    for the sampled ones, a Pallas count kernel for the per-leaf kernel
    path. Parameterizing the count is what keeps all three paths walking
    the *same* pinned iterate sequence.

    ``warm`` (§5.2.2 pushed further): the previous step's converged
    threshold. It is first probed — if its count is already in band the
    search exits with zero iterations — otherwise its ratio coordinate
    seeds the bracket (``(0, r_prev)`` when the count fell below ``k``,
    ``(r_prev, 1)`` when above ``2k``), shrinking the cold ``(0, 1)``
    interval to the residual drift since last step. ``warm=None`` is the
    cold search, bitwise-identical to the pre-warm-start code.
    """
    def in_band(n):
        return jnp.logical_and(n >= k, n <= 2 * k)

    if warm is None:
        l0, r0 = jnp.float32(0.0), jnp.float32(1.0)
        nnz0 = jnp.int32(-1)
    else:
        nnz0 = count_at(warm)
        accept = in_band(nnz0)
        r_prev = warm_ratio(warm, mean, mx)
        l0 = jnp.where(nnz0 > 2 * k, r_prev, jnp.float32(0.0))
        r0 = jnp.where(nnz0 < k, r_prev, jnp.float32(1.0))

    def cond(state):
        l, r, nnz = state
        return jnp.logical_and(~in_band(nnz), (r - l) > eps)

    def body(state):
        l, r, _ = state
        ratio = bisect_midpoint(l, r)
        nnz = count_at(threshold_at(mean, mx, ratio))
        # nnz too small -> threshold too high -> move right bound down
        r = jnp.where(nnz < k, ratio, r)
        l = jnp.where(nnz > 2 * k, ratio, l)
        return l, r, nnz

    l, r, _ = jax.lax.while_loop(cond, body, (l0, r0, nnz0))
    thr = threshold_at(mean, mx, bisect_midpoint(l, r))
    if warm is not None:
        thr = jnp.where(accept, warm, thr)
    return thr


def threshold_binary_search(
    x: jax.Array,
    k: int,
    eps: float = 1e-3,
    threshold: jax.Array | None = None,
    *,
    warm: jax.Array | None = None,
) -> tuple[Selected, jax.Array]:
    """Binary-search a threshold t with k <= nnz(|x|>t) <= 2k.

    Returns the selection *and* the threshold so callers can implement the
    paper's "sampled" variant (reuse the threshold for the next `interval`
    iterations via ``threshold_filter``). capacity == 2k.

    ``threshold`` short-circuits the whole search (§5.2.2 reuse): the
    cached threshold is applied directly, no statistics and no bisection
    are traced. ``warm`` seeds the bisection bracket from the previous
    converged threshold (see ``search_band``) while still re-searching.
    """
    if threshold is not None:
        # Reuse branch: filter at the cached threshold. (This used to run
        # the full bisection while_loop and then discard its result.)
        return threshold_filter(x, threshold, capacity=2 * k), threshold
    ax = jnp.abs(x)
    mean, mx = _stats(ax)
    thr = search_band(lambda t: jnp.sum(ax > t), mean, mx, k, eps, warm)
    return threshold_filter(x, thr, capacity=2 * k), thr


def sampled_threshold_search(
    x: jax.Array,
    k: int,
    *,
    stride: int,
    capacity: int,
    eps: float = 1e-3,
    warm: jax.Array | None = None,
) -> tuple[Selected, jax.Array]:
    """DGC-style sampled Alg 3: search on a strided subsample of ``x``.

    Statistics (mean/max) and every bisection count come from
    ``x[::stride]`` — an O(n/stride) scan per iteration instead of O(n) —
    with the subsample count scaled by ``stride`` as the nnz estimate.
    Only the final filter touches the full vector, so its ``count``
    header is the *true* survivor count and its ``overflow`` flag catches
    under-estimates that blow past ``capacity`` (the caller sizes
    ``capacity`` with tolerance headroom; ``cost_model.sample_stride``
    derives ``stride`` from ``k`` and the documented tolerance).
    ``stride=1`` is bitwise-identical to ``threshold_binary_search``.
    """
    flat = x.reshape(-1)
    xs = flat[::stride] if stride > 1 else flat
    axs = jnp.abs(xs)
    mean, mx = _stats(axs)
    thr = search_band(lambda t: jnp.sum(axs > t) * stride,
                      mean, mx, k, eps, warm)
    return threshold_filter(x, thr, capacity=capacity), thr


def threshold_filter(x: jax.Array, threshold: jax.Array, capacity: int) -> Selected:
    """All elements with |x| > threshold, first-`capacity`, padded (Alg 5 L40).

    Overflow semantics (pinned): when ``nnz > capacity`` the first
    ``capacity`` survivors in *index* order are kept — lowest indices
    win, NOT the largest magnitudes — the ``count`` header saturates at
    ``capacity``, and ``overflow`` is set to flag the silent drop.
    Shapes are static, so the alternative — growing the message — does
    not exist; the flag is the contract.
    """
    ax = jnp.abs(x)
    mask = ax > threshold
    nnz = jnp.sum(mask)
    (idx,) = jnp.nonzero(mask, size=capacity, fill_value=x.size)
    safe = jnp.minimum(idx, x.size - 1)
    vals = jnp.where(idx < x.size, x[safe], 0.0)
    return Selected(idx.astype(jnp.int32), vals, jnp.minimum(nnz, capacity),
                    nnz > capacity)


# ---------------------------------------------------------------------------
# Quantized variants (§5.2.3): same-signed communication sets
# ---------------------------------------------------------------------------

def _signed_score(x: jax.Array, phase: jax.Array) -> jax.Array:
    """Score for alternating top/bottom selection.

    phase == 0 -> select largest values (positives); phase == 1 -> most
    negative values. Elements of the wrong sign get score 0 so they are never
    selected ahead of a same-signed element.
    """
    y = jnp.where(phase == 0, x, -x)
    return jnp.maximum(y, 0.0)


def exact_topk_quant(x: jax.Array, k: int, phase: jax.Array) -> Selected:
    score = _signed_score(x, phase)
    sel = _pad_topk(x, score, k)
    return _quantize(sel, x.size)


def trimmed_topk_quant(
    x: jax.Array, k: int, phase: jax.Array, eps: float = 0.2
) -> Selected:
    score = _signed_score(x, phase)
    mean, mx = _stats(score)

    def cond(state):
        step, nnz = state
        return jnp.logical_and(nnz < k, ladder_ratio(step, eps) > 0.0)

    def body(state):
        step, _ = state
        step = step + 1
        thr = threshold_at(mean, mx, ladder_ratio(step, eps))
        return step, jnp.sum(score > thr)

    step0 = jnp.int32(1)
    nnz0 = jnp.sum(score > threshold_at(mean, mx, ladder_ratio(step0, eps)))
    step, _ = jax.lax.while_loop(cond, body, (step0, nnz0))
    thr = threshold_at(mean, mx, ladder_ratio(step, eps))
    sel = _pad_topk(x, jnp.where(score > thr, score, 0.0), k)
    return _quantize(sel, x.size)


def threshold_binary_search_quant(
    x: jax.Array, k: int, phase: jax.Array, eps: float = 1e-3
) -> Selected:
    """Binary-search variant on the signed score, then quantize.

    The paper notes threshold *sharing* is incompatible with quantization
    (the sign phase alternates every iteration), so no threshold is returned.
    """
    score = _signed_score(x, phase)
    mean, mx = _stats(score)
    thr = search_band(lambda t: jnp.sum(score > t), mean, mx, k, eps)
    mask = score > thr
    nnz = jnp.sum(mask)
    (idx,) = jnp.nonzero(mask, size=2 * k, fill_value=x.size)
    safe = jnp.minimum(idx, x.size - 1)
    vals = jnp.where(idx < x.size, x[safe], 0.0)
    sel = Selected(idx.astype(jnp.int32), vals, jnp.minimum(nnz, 2 * k),
                   nnz > 2 * k)
    return _quantize(sel, x.size)


def _quantize(sel: Selected, size: int) -> Selected:
    """Replace per-element values by their mean (broadcast at decompression).

    The mean is stored in values[0]; the rest of the value payload is unused
    on the wire (sync.py transmits only (count, indices, mean) for quantized
    messages). Values here are reconstructed dense so masking/decompression
    code paths stay uniform.
    """
    valid = sel.indices < size
    total = pinned_sum(jnp.where(valid, sel.values, 0.0))
    mean = mean_of_sum(total, jnp.maximum(sel.count, 1))
    return Selected(sel.indices, jnp.where(valid, mean, 0.0), sel.count,
                    sel.overflow)
