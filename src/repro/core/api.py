"""Composable gradient-compression API (RedSync decomposed).

The paper's pipeline — residual accumulation → communication-set selection
→ packing → sparse allgather → decompression → apply — is decomposed into
four swappable protocols, each string-addressable via
``repro.core.registry``:

``Compressor``
    Per-leaf selection/decompression policy. ``compress`` maps a flat f32
    residual vector to a fixed-capacity ``Selected`` set (plus updated
    ``LeafState`` — threshold cache, quantization phase); ``decompress``
    turns gathered wire messages back into a dense f32 update sum.
    Implementations: ``dense``, ``exact_topk``, ``trimmed_topk`` (Alg 2),
    ``threshold_bsearch`` (Alg 3), and the ``quantized(inner)`` wrapper
    (§5.2.3).

``Transport``
    Wire packing + collectives over ``sync_axes``. Implementations:
    ``fused_allgather`` (§5.3 tensor fusion: one collective for all
    leaves), ``bucketed_allgather`` (§5.3 fusion under a fixed byte
    budget — one collective per bucket), ``hierarchical`` (§5.4
    intra-node dense psum + inter-node sparse allgather on a 2-axis
    mesh), ``per_leaf_allgather``, and ``dense_psum`` (dense baseline —
    sparse messages are a configuration error). Every transport carries a
    ``StageTimer`` hook for instrumentation-grade counters.

``StageTimer``
    Stage instrumentation hook threaded through ``GradientSync.update``
    and the transports: each pipeline stage (``accumulate`` / ``select``
    / ``mask`` / ``pack`` / ``transfer`` / ``unpack`` — Fig 10's
    decomposition with the paper's "mask" bar split) runs inside
    ``timer.stage(name, thunk)``, which runs it under
    ``jax.named_scope(name)`` so the compiled step names each stage;
    ``timer.count`` records ``dispatch_<stage>`` fused-operation launches
    and transport collective/message counts. ``repro.core.instrument``
    ships ``NullTimer`` (free, trace-safe default) and ``WallClockTimer``
    (barriered wall-clock sampling for eager benchmark runs).

``DispatchPolicy``
    Chooses a compressor *name* per leaf. ``size_based`` is the paper's
    §5.5 byte-size dispatch (using real ``dtype.itemsize`` bytes);
    ``fixed`` routes every leaf through one named compressor.

``Schedule``
    The §5.6 overlap scheduler: owns the ORDER in which
    ``GradientSync.update`` compresses, dispatches and applies its sync
    units, and any cross-step double buffering. ``sequential`` is the
    historical full-tree barrier; ``chunked`` partitions the tree into
    reverse-parameter-order chunks and dispatches each chunk's
    collective as soon as its gradients are processed (bitwise
    identical results, >= 2 transport dispatches per step); ``stale1``
    communicates step *t-1*'s compressed residual during step *t*
    (double-buffered, one step of sparse staleness). Implementations in
    ``repro.core.overlap``.

``Correction``
    Convergence-preserving transforms (Deep Gradient Compression, Lin et
    al. 1712.01887) that run AHEAD of any registered compressor:
    gradient pre-transforms (``local_clip``), residual accumulation
    ownership (``momentum`` correction), post-selection state masking
    (``factor_masking``), and the density warm-up ramp (``warmup``).
    Implementations in ``repro.core.correction``; composed via the
    extended spec grammar, e.g. ``"momentum+clip(threshold_bsearch)"``.

``GradientSync`` (repro.core.gradient_sync) composes the four into an
optax-style ``init(params)`` / ``update(grads, state, params, lr)``
transform; ``rgc_apply`` is now a thin shim over it.

These are structural ``Protocol``s: implementations register with the
registry and need not inherit anything.
"""
from __future__ import annotations

from typing import Any, Protocol, runtime_checkable

import jax

from .residual import LeafState
from .selection import Selected


@runtime_checkable
class Compressor(Protocol):
    """Per-leaf compression: residual vector -> sparse communication set.

    Compressors MAY additionally set ``supports_segmented = True`` and
    provide ``compress_segments(arena2d, geometry, states, stats)`` —
    the same selection over every slot of a flat residual arena at once
    (``repro.core.arena``); ``GradientSync`` fuses only leaves whose
    compressor does, and falls back per leaf otherwise.
    """

    name: str
    quantized: bool      # wire payload is (count, indices, mean) if True

    def capacity(self, k: int) -> int:
        """Fixed message capacity (trace-time shape) for a target of k."""
        ...

    def init_leaf(self, param: jax.Array, *, momentum: bool,
                  residual_dtype: Any) -> LeafState:
        """Per-leaf residual/momentum/threshold state."""
        ...

    def compress(self, flat_v: jax.Array, k: int,
                 state: LeafState) -> tuple[Selected, LeafState]:
        """Select the communication set from the flat f32 residual."""
        ...

    def decompress(self, gathered: jax.Array, size: int,
                   k: int) -> jax.Array:
        """[workers, msg_len] wire messages -> dense f32[size] update SUM."""
        ...


@runtime_checkable
class StageTimer(Protocol):
    """Pipeline stage instrumentation (Fig 10's mask/select/pack/transfer/
    unpack decomposition). ``stage`` executes a stage body under
    ``jax.named_scope(name)``, so the compiled step and its profiler
    trace name the stage, and may time it; ``count`` records
    dimensionless facts (collectives per step, bucket counts).
    Implementations: ``instrument.NullTimer`` (default; scope only, safe
    under tracing) and ``instrument.WallClockTimer`` (eager-mode
    barriered timing)."""

    active: bool

    def stage(self, name: str, thunk: Any) -> Any:
        """Run ``thunk()`` as pipeline stage ``name`` (under the named
        scope ``name``); return its value."""
        ...

    def count(self, name: str, n: int = 1) -> None:
        """Accumulate a counter (no barrier, no timing)."""
        ...

    def set_lane(self, lane: str | None) -> None:
        """Attribute subsequent stages to a lane (e.g. ``"chunk0"`` —
        the per-chunk attribution of the ``chunked`` schedule); ``None``
        returns to the unlaned default."""
        ...

    def summary(self) -> dict:
        """Collected per-stage timings/counters ({} for null timers)."""
        ...


@runtime_checkable
class Transport(Protocol):
    """Wire packing + collectives over the data-parallel mesh axes."""

    name: str
    sync_axes: tuple[str, ...]
    timer: Any            # StageTimer hook (NullTimer when unset)

    def num_workers(self) -> int:
        """Product of ``sync_axes`` sizes (1 outside any mesh)."""
        ...

    def pack(self, sel: Selected, quantized: bool) -> jax.Array:
        """Selected -> packed f32 wire message."""
        ...

    def allgather(self, messages: list[jax.Array]) -> list[jax.Array]:
        """Exchange packed messages; returns per-leaf [workers, len]."""
        ...

    def allreduce_mean(self, grad: jax.Array) -> jax.Array:
        """Dense fallback for small leaves (psum / pmean)."""
        ...


@runtime_checkable
class DispatchPolicy(Protocol):
    """Per-leaf compressor choice (the §5.5 method dispatch, pluggable).

    ``compressor_for`` receives the RAW gradient leaf (pre-correction, so
    byte-size dispatch sees the parameter's true storage dtype) and must
    depend only on its path/shape/dtype — the decision is cached per
    (treedef, leaf signature, density) and leaves are tracers under jit,
    so value-dependent dispatch was never expressible anyway.
    """

    def compressor_for(self, path: str, leaf: jax.Array) -> str:
        """Registered compressor name for this leaf ("dense" = allreduce)."""
        ...


@runtime_checkable
class Correction(Protocol):
    """Convergence correction run ahead of any compressor (DGC lineage).

    ``GradientSync.update`` folds every configured correction through four
    hooks, in pipeline order: ``on_grads`` (tree-level gradient transform,
    pre-accumulation), ``accumulate`` (optional ownership of a leaf's
    residual update — first correction returning non-None wins; None means
    "not mine" and core falls back to plain ``V += g``),
    ``on_communicated`` (state masking after selection; the residual is
    already cleared), and ``density_at`` (the warm-up schedule; None means
    "no schedule owned here"). ``repro.core.correction.CorrectionBase``
    provides no-op defaults for all four.
    """

    name: str
    needs_momentum_buffer: bool   # allocate param-shaped LeafState.momentum

    def on_grads(self, grads: list[jax.Array], params: list[jax.Array],
                 num_workers: int) -> list[jax.Array]:
        """Transform the whole local gradient list before accumulation."""
        ...

    def accumulate(self, grad: jax.Array, param: jax.Array,
                   state: LeafState, *,
                   weight_decay: float) -> LeafState | None:
        """Fold this leaf's gradient into its residual; None = pass."""
        ...

    def on_communicated(self, state: LeafState,
                        indices: jax.Array) -> LeafState:
        """Mask leaf state at communicated coordinates (padding-safe)."""
        ...

    def density_at(self, step: int, target: float) -> float | None:
        """Scheduled density at ``step``; None = no schedule owned here."""
        ...


@runtime_checkable
class Schedule(Protocol):
    """Overlap scheduler (§5.6): the dispatch order of one sync step.

    ``GradientSync`` delegates its whole ``init``/``update`` orchestration
    here: ``init_state`` may wrap the params-congruent LeafState tree
    (``stale1`` adds the double-buffered pending messages —
    ``overlap.ScheduleState``), and ``step`` drives the pipeline through
    the ``GradientSync`` stage helpers (``_compress_plan`` / ``_gather``
    / ``_apply_gathered`` / ``_dense_reduce`` / ``_dense_apply``),
    deciding how the work is chunked, when each transport collective is
    dispatched, and which step's messages it carries. Implementations:
    ``repro.core.overlap`` (``sequential`` / ``chunked`` / ``stale1``),
    registry kind ``registry.SCHEDULE``.
    """

    name: str

    def init_state(self, sync: Any, params: Any, leaf_state: Any) -> Any:
        """Wrap (or pass through) the LeafState tree as the full state."""
        ...

    def step(self, sync: Any, grads: Any, state: Any, params: Any,
             lr: jax.Array, density: float) -> tuple[Any, Any]:
        """One synchronized step; returns (new_params, new_state)."""
        ...

    def wrap_state_specs(self, leaf_specs: Any, replicated: Any) -> Any:
        """Partition specs congruent with ``init_state``'s wrapping:
        given the LeafState tree's specs and a replicated (prefix) spec
        for any schedule-owned buffers, return the full state's specs
        (the trainer's shard_map/jit plumbing)."""
        ...
