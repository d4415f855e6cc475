"""``GradientSync``: the composed RedSync pipeline (Algorithms 4 + 5).

Optax-style transform built from three registry-addressable pieces:

    sync = build_gradient_sync(optimizer="rgc", sync_axes=("data",), ...)
    state = sync.init(params)
    new_params, new_state = sync.update(grads, state, params, lr)

``update`` runs the paper's six stages per step — DGC local clipping →
residual/momentum accumulation → selection (``Compressor``) → packing +
sparse allgather (``Transport``) → scatter-add decompression → SGD apply
— with the per-leaf method choice owned by a ``DispatchPolicy``.
``density >= 1.0`` is the §5.7 dense-warm-up sentinel: every leaf takes
the dense allreduce path regardless of policy.

With ``fuse_leaves`` (default) the sparse path runs over FLAT RESIDUAL
ARENAS (``repro.core.arena``): leaves sharing a gradient dtype and a
segmented compressor coalesce into contiguous f32 arenas; the mask /
pack stages each issue ONE fused operation per arena instead of one per
leaf, and select goes further — ALL arenas of a step search together in
one ``kernels.segmented.multi_select`` (a single count launch per
search iteration for every segment of every arena) — O(arenas) -> O(1)
dispatches for the Fig 10 overhead stages — while selection stays
segmented per leaf, so the communicated set, params and optimizer state
are bitwise identical to the per-leaf path. The static per-step plan (paths, dispatch, k targets, arena
layout) is cached per (treedef, leaf signature, density).

The ORDER of one step's dispatches is owned by a ``Schedule``
(``repro.core.overlap``, ``TrainConfig.schedule``): ``sequential`` is
the historical compress-all → one-transfer → apply barrier; ``chunked``
partitions the tree into reverse-parameter-order chunks (§5.6 — the
order backprop emits gradients) and dispatches each chunk's collective
as soon as that chunk is packed, bitwise identical to sequential;
``stale1`` double-buffers the packed messages and communicates step
*t-1*'s buffer during step *t*.

Like the legacy ``rgc_apply`` it replaces (now a shim over this), it must
run inside a fully-manual shard_map region whose axis names include the
transport's ``sync_axes``; every leaf is a raw local shard and gradients
are local (un-averaged).

``optimizer`` accepts ``"rgc"`` (§5.5 size-based dispatch), ``"rgc_quant"``
(same + §5.2.3 quantization), ``"dense"``, or ANY registered compressor
spec — e.g. ``"threshold_bsearch"`` or ``"quantized(trimmed_topk)"`` —
which routes every leaf through that compressor. The spec may additionally
prefix ``+``-joined DGC ``Correction`` names that run ahead of whatever
compressor dispatch picks: ``"momentum+clip(threshold_bsearch)"`` is
momentum correction → local clipping → Alg 3 selection on every leaf, and
``"warmup(rgc)"`` ramps density over the §5.5 dispatch (see
``repro.core.correction``). Spec corrections are additive: the
``momentum`` / ``local_clip`` config fields stay the on/off switches for
their corrections whether or not the spec names them, so legacy specs and
``rgc_apply`` keep bitwise parity and ``"warmup(rgc)"`` is exactly
``"rgc"`` plus the ramp.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from . import arena
from . import registry
from .api import Compressor, Correction, DispatchPolicy, Transport
from .compressors import _Base as _CompressorBase  # noqa: F401 (registration)
from .correction import LocalClip, MomentumCorrection, split_corrections
from .dispatch import FixedPolicy, SizeBasedPolicy, leaf_nbytes
from .instrument import NullTimer
from .overlap import SequentialSchedule, make_schedule, partition_chunks
from .residual import (LeafState, accumulate, accumulate_arena,
                       mask_communicated)
from .sync import message_len
from .transport import DEFAULT_BUCKET_BYTES
from .transport import FusedAllgather  # noqa: F401 (registration)


class _StepPlan(NamedTuple):
    """Static per-step dispatch plan, cached per (treedef, leaf signature,
    density, all_dense) — paths, compressor choices, k targets and the
    arena layout never change within a trace, so they are computed once
    instead of per update call."""

    paths: tuple[str, ...]
    dense: tuple[int, ...]                              # dense-path leaves
    sparse: tuple[tuple[int, Any, int], ...]            # per-leaf (i, comp, k)
    groups: tuple[arena.ArenaGroup, ...]                # fused arenas
    group_comps: tuple[Any, ...]                        # compressor per group


def _by_leaf(group: arena.ArenaGroup, states: list,
             fld: str) -> dict[int, Any]:
    """Leaf-indexed view of per-slot state fields (what ``arena.gather``
    consumes)."""
    return {slot.leaf: getattr(st, fld)
            for slot, st in zip(group.slots, states)}


@dataclass
class GradientSync:
    """Composed residual-gradient-compression transform."""

    policy: DispatchPolicy
    transport: Transport
    density: float = 0.001
    momentum: float = 0.9
    nesterov: bool = False
    weight_decay: float = 0.0
    local_clip: float | None = None
    quantize: bool = False
    no_quant_paths: tuple[str, ...] = ("lm_head", "embed")
    residual_dtype: Any = jnp.float32
    # Flat residual arenas: coalesce same-dtype sparse leaves that share a
    # segmented compressor into contiguous f32 arenas, so mask / pack run
    # once per ARENA and select once per STEP (all arenas fused into one
    # multi_select; see repro.core.arena). Selection
    # stays segmented per leaf — the communicated set, params and state
    # are bitwise identical to the per-leaf path. Leaves without a
    # segmented compressor (exact_topk, quantized) and pipelines with
    # non-arena-safe custom corrections fall back per leaf automatically.
    fuse_leaves: bool = True
    # Also run residual accumulation as ONE fused pass per arena (the
    # single-launch residual-update+stats kernel of kernels/segmented.py)
    # instead of per leaf. Off by default: the momentum / weight-decay
    # products may differ from the per-leaf graph by <= 1 ulp when XLA
    # FMA-contracts one side, so this trades bitwise reproducibility vs
    # the per-leaf path for one fewer HBM round-trip (exact when
    # momentum == weight_decay == 0).
    fuse_accumulate: bool = False
    # DGC corrections run ahead of any compressor, in order. Spec-named
    # corrections land here explicitly; the momentum / local_clip config
    # fields ALWAYS imply their corrections (those fields are the on/off
    # switches — legacy semantics), appended unless the same name was
    # already given, so e.g. "warmup(rgc)" keeps momentum correction on
    # sparse leaves consistent with the dense-leaf momentum SGD.
    corrections: tuple[Correction, ...] | None = None
    # parameter bag threaded to compressor factories (backend,
    # bsearch_interval, trim_eps, ...)
    compressor_params: dict = field(default_factory=dict)
    # §5.6 overlap scheduler (core.overlap): owns the dispatch order of
    # the step — "sequential" full-tree barrier (default), "chunked"
    # per-chunk pipelined dispatch, "stale1" one-step-delayed double
    # buffering. None -> SequentialSchedule.
    schedule: Any = None
    # byte budget of one "chunked" pipeline chunk (raw gradient bytes;
    # shares the bucket_bytes knob/default with the bucketed transport)
    chunk_bytes: int = DEFAULT_BUCKET_BYTES
    # stage-timer hook (core.instrument): NullTimer (free, trace-safe) by
    # default; bench_transport swaps in a WallClockTimer for eager runs
    timer: Any = None
    _compressors: dict = field(default_factory=dict, repr=False)
    _plans: dict = field(default_factory=dict, repr=False)

    def __post_init__(self) -> None:
        if self.timer is None:
            self.timer = NullTimer()
        if self.schedule is None:
            self.schedule = SequentialSchedule()
        corr = list(self.corrections or ())
        names = {c.name for c in corr}
        if self.local_clip is not None and "local_clip" not in names:
            corr.insert(0, LocalClip(self.local_clip))
        if self.momentum and "momentum" not in names:
            corr.append(MomentumCorrection(self.momentum, self.nesterov))
        self.corrections = tuple(corr)
        # arenas only fuse pipelines whose corrections they reproduce
        # exactly; a custom correction with bespoke per-leaf hooks drops
        # the whole pipeline back to the per-leaf path (never silently
        # changes results)
        self._arena_ok = all(
            getattr(c, "arena_safe", lambda: False)()
            for c in self.corrections)

    # -- construction helpers ----------------------------------------------

    def compressor(self, name: str) -> Compressor:
        """Resolve (and cache) a compressor instance by registered name."""
        if name not in self._compressors:
            self._compressors[name] = registry.make(
                registry.COMPRESSOR, name, **self.compressor_params)
        return self._compressors[name]

    def _leaf_compressor(self, name: str, path: str) -> Compressor:
        """Apply the §5.2.3 quantization wrap where configured.

        The output/embedding layers are never quantized ("we do not
        quantify the output layer").
        """
        if (self.quantize and name != "dense"
                and not name.startswith("quantized")
                and not any(t in path for t in self.no_quant_paths)):
            return self.compressor(f"quantized({name})")
        return self.compressor(name)

    @property
    def uses_momentum_buffer(self) -> bool:
        """Whether leaf states carry a param-shaped velocity (vs scalar)."""
        return bool(self.momentum) or any(
            getattr(c, "needs_momentum_buffer", False)
            for c in self.corrections)

    def scheduled_density(self, step: int) -> float | None:
        """Warm-up density at ``step`` from a schedule-owning correction
        (``warmup``); None when no correction owns a schedule."""
        for c in self.corrections:
            d = c.density_at(step, self.density)
            if d is not None:
                return d
        return None

    def _accumulate(self, grad: jax.Array, param: jax.Array,
                    state: LeafState) -> LeafState:
        """Residual accumulation: first owning correction wins, else V += g."""
        for c in self.corrections:
            st = c.accumulate(grad, param, state,
                              weight_decay=self.weight_decay)
            if st is not None:
                return st
        return accumulate(grad, param, state, momentum=0.0, nesterov=False,
                          weight_decay=self.weight_decay)

    # -- the transform ------------------------------------------------------

    def init(self, params: Any) -> Any:
        """Optimizer state for ``params``.

        The base is a params-congruent tree of ``LeafState`` — each
        leaf's state comes from the compressor the policy assigns it
        (all built-ins share ``residual.init_leaf``; custom compressors
        may carry extra state). The schedule may wrap it: ``stale1``
        returns an ``overlap.ScheduleState`` carrying the zero-count
        pending message buffers alongside the leaf tree.
        """
        leaves, treedef = jax.tree.flatten(params)
        paths = [jax.tree_util.keystr(kp) for kp, _ in
                 jax.tree_util.tree_flatten_with_path(params)[0]]
        out = []
        for path, p in zip(paths, leaves):
            name = self.policy.compressor_for(path, p)
            comp = self._leaf_compressor(name, path)
            out.append(comp.init_leaf(p, momentum=self.uses_momentum_buffer,
                                      residual_dtype=self.residual_dtype))
        leaf_state = jax.tree.unflatten(treedef, out)
        return self.schedule.init_state(self, params, leaf_state)

    # -- the per-step plan (cached; satellite of the arena refactor) --------

    def _plan(self, grads: Any, treedef: Any, leaves_g: list,
              density: float, all_dense: bool) -> _StepPlan:
        """Resolve (and cache) the static dispatch plan for this step.

        Paths, per-leaf compressor choices, ``k`` targets and the arena
        layout depend only on the tree structure, leaf shapes/dtypes and
        the density — all static per trace — so ``keystr`` /
        ``compressor_for`` / ``ceil`` run once per (treedef, signature,
        density, all_dense) instead of on every call.
        """
        sig = tuple((tuple(g.shape), str(g.dtype)) for g in leaves_g)
        key = (treedef, sig, density, all_dense)
        if key in self._plans:
            return self._plans[key]

        paths = [jax.tree_util.keystr(kp) for kp, _ in
                 jax.tree_util.tree_flatten_with_path(grads)[0]]
        plan = self._plan_leaves(range(len(leaves_g)), paths, leaves_g,
                                 density, all_dense)
        self._plans[key] = plan
        return plan

    def _plan_leaves(self, indices, paths, leaves_g, density: float,
                     all_dense: bool, aid_base: int = 0) -> _StepPlan:
        """Dispatch plan restricted to the leaves in ``indices`` (in the
        given order) — the whole tree for the sequential plan, one
        chunk's leaves for the chunked schedule's per-chunk plans.
        Arena grouping happens WITHIN the index set, so a chunk's fused
        operations touch only that chunk's leaves."""
        dense: list[int] = []
        sparse: list[tuple[int, Compressor, int]] = []
        fusable: dict[tuple[str, str], list] = {}
        for i in indices:
            g = leaves_g[i]
            name = ("dense" if all_dense
                    else self.policy.compressor_for(paths[i], g))
            if name == "dense":
                dense.append(i)
                continue
            k = max(1, int(math.ceil(density * g.size)))
            comp = self._leaf_compressor(name, paths[i])
            if (self.fuse_leaves and self._arena_ok
                    and getattr(comp, "supports_segmented", False)
                    and not comp.quantized):
                cap = comp.capacity(k)
                fusable.setdefault((comp.name, str(g.dtype)), []).append(
                    (i, paths[i], int(g.size), k, cap,
                     message_len(cap, False)))
            else:
                sparse.append((i, comp, k))

        groups, group_comps = [], []
        for aid, ((name, dtype), slots) in enumerate(fusable.items()):
            groups.append(arena.build_group(aid_base + aid, name, dtype,
                                            slots))
            group_comps.append(self.compressor(name))

        return _StepPlan(paths=tuple(paths[i] for i in indices),
                         dense=tuple(dense), sparse=tuple(sparse),
                         groups=tuple(groups),
                         group_comps=tuple(group_comps))

    def _chunk_plans(self, grads: Any, treedef: Any, leaves_g: list,
                     density: float,
                     all_dense: bool) -> tuple[_StepPlan, ...]:
        """Per-chunk dispatch plans for the ``chunked`` schedule (cached).

        ``overlap.partition_chunks`` splits the leaf set into
        reverse-parameter-order chunks under ``chunk_bytes`` (raw
        gradient bytes); each chunk then gets its own ``_plan_leaves``
        plan, so arenas never span a chunk boundary and every chunk's
        select/mask/pack feeds its own transport dispatch."""
        sig = tuple((tuple(g.shape), str(g.dtype)) for g in leaves_g)
        key = (treedef, sig, density, all_dense, "chunked",
               self.chunk_bytes)
        if key in self._plans:
            return self._plans[key]

        paths = [jax.tree_util.keystr(kp) for kp, _ in
                 jax.tree_util.tree_flatten_with_path(grads)[0]]
        chunks = partition_chunks([leaf_nbytes(g) for g in leaves_g],
                                  self.chunk_bytes)
        plans = tuple(
            self._plan_leaves(c.leaves, paths, leaves_g, density,
                              all_dense, aid_base=1000 * c.cid)
            for c in chunks)
        self._plans[key] = plans
        return plans

    def _pending_zeros(self, params: Any) -> tuple[jax.Array, ...]:
        """Zero-count wire messages matching the target-density plan's
        unit order (arena groups, then per-leaf sparse units) — the
        ``stale1`` schedule's initial double buffer. An all-zeros f32
        message decodes as count == 0, so applying it is a no-op."""
        leaves, treedef = jax.tree.flatten(params)
        plan = self._plan(params, treedef, leaves, self.density,
                          self.density >= 1.0)
        pending = [jnp.zeros((g.msg_total,), jnp.float32)
                   for g in plan.groups]
        pending += [jnp.zeros((message_len(comp.capacity(k),
                                           comp.quantized),), jnp.float32)
                    for _, comp, k in plan.sparse]
        return tuple(pending)

    def _arena_coeffs(self) -> tuple[float, bool]:
        """(momentum, nesterov) of the accumulation-owning correction —
        mirrors ``_accumulate``'s first-owner-wins rule for arenas."""
        for c in self.corrections:
            coeffs = getattr(c, "arena_coeffs", lambda: None)()
            if coeffs is not None:
                return coeffs
        return 0.0, False

    def _accumulate_group(self, group: arena.ArenaGroup, comp: Compressor,
                          leaves_g: list, leaves_p: list, leaves_s: list
                          ) -> tuple:
        """The accumulate phase of one fused arena step: residual update
        -> gather into the arena's 2-D view. Returns
        ``(v2d, u2d, stats, states_in)`` for the fused select phase.

        Residual accumulation defaults to the per-leaf hook chain
        (``_accumulate``) — its momentum product is the one piece of
        float arithmetic whose XLA FMA-contraction decision depends on
        the surrounding graph, so keeping the exact per-leaf subgraph is
        what makes the fused path BITWISE identical under jit. With
        ``fuse_accumulate`` the arena instead runs the single-pass fused
        residual-update+stats kernel (one HBM round-trip, O(arenas)
        dispatches) whose momentum product may differ from the per-leaf
        graph by <= 1 ulp when XLA contracts one side to an FMA — exact
        when ``momentum == 0`` and ``weight_decay == 0``.
        """
        timer = self.timer
        geom = group.geometry
        m, nesterov = self._arena_coeffs()
        use_pallas = getattr(comp, "backend", "jnp") == "pallas"
        mask_u = any(getattr(c, "arena_mask_momentum", False)
                     for c in self.corrections)
        need_u = self.uses_momentum_buffer and bool(m or mask_u)
        rd = (None if self.residual_dtype == jnp.float32
              else self.residual_dtype)

        if self.fuse_accumulate:
            def _acc():
                g2d = arena.gather(group, leaves_g)
                v2d = arena.gather(group, [s.residual for s in leaves_s])
                u2d = (arena.gather(group,
                                    [s.momentum for s in leaves_s])
                       if need_u else None)
                p2d = (arena.gather(group, leaves_p)
                       if self.weight_decay else None)
                if use_pallas:
                    from repro.kernels import segmented as kseg
                    v2, u2, sums, maxs = kseg.seg_residual_update_stats(
                        g2d, v2d, u2d if m else None, p2d, geom.block_seg,
                        geom.n_seg, momentum=m, nesterov=nesterov,
                        weight_decay=self.weight_decay, round_dtype=rd)
                    stats = (kseg.seg_mean(sums, geom), maxs)
                else:
                    v2, u2 = accumulate_arena(
                        g2d, v2d, u2d if m else None, p2d, momentum=m,
                        nesterov=nesterov, weight_decay=self.weight_decay,
                        residual_dtype=self.residual_dtype)
                    stats = None
                states = [leaves_s[slot.leaf] for slot in group.slots]
                return v2, (u2 if u2 is not None else u2d), stats, states

            timer.count("dispatch_accumulate")
            v2d, u2d, stats, states_in = timer.stage("accumulate", _acc)
        else:
            def _acc():
                states = []
                for slot in group.slots:
                    timer.count("dispatch_accumulate")
                    states.append(self._accumulate(
                        leaves_g[slot.leaf], leaves_p[slot.leaf],
                        leaves_s[slot.leaf]))
                v2d = arena.gather(group, _by_leaf(group, states,
                                                   "residual"))
                u2d = (arena.gather(group, _by_leaf(group, states,
                                                    "momentum"))
                       if need_u else None)
                return v2d, u2d, None, states

            v2d, u2d, stats, states_in = timer.stage("accumulate", _acc)

        return v2d, u2d, stats, states_in

    def _select_groups(self, groups, comps, accs) -> list[tuple]:
        """The fused select phase: Alg 2/3 across EVERY arena of the step
        in one ``multi_select`` call — a single count/compact dispatch
        per search iteration for all segments of all arenas (mixed
        backends partition into one call per backend). Returns one
        ``(selected, slot_states)`` pair per group.

        Compressors that predate the ``segment_spec`` protocol (custom
        subclasses overriding only ``compress_segments``) fall back to
        their own per-group call, preserving behavior at per-arena
        dispatch granularity.
        """
        from repro.kernels import segmented as kseg
        results: list[tuple | None] = [None] * len(groups)
        by_backend: dict[bool, list[int]] = {}
        for i, (group, comp) in enumerate(zip(groups, comps)):
            v2d, _u2d, stats, states_in = accs[i]
            try:
                spec = comp.segment_spec(group.geometry, states_in)
            except NotImplementedError:
                sel, slot_states = comp.compress_segments(
                    v2d, group.geometry, states_in, stats)
                results[i] = (sel, slot_states)
                continue
            by_backend.setdefault(
                getattr(comp, "backend", "jnp") == "pallas", []).append(
                    (i, spec))
        for use_pallas, entries in by_backend.items():
            parts = [(accs[i][0], groups[i].geometry, spec, accs[i][2])
                     for i, spec in entries]
            out = kseg.multi_select(parts, use_pallas=use_pallas)
            for (i, _spec), (sel, thr) in zip(entries, out):
                results[i] = (sel, comps[i].finish_segments(accs[i][3], thr))
        return results

    def _finish_group(self, group: arena.ArenaGroup, comp: Compressor,
                      selected: list, slot_states: list, v2d: jax.Array,
                      u2d: jax.Array | None, leaves_p: list,
                      new_states: list) -> jax.Array:
        """The post-select phase of one fused arena step: mask -> scatter
        state back -> pack; returns the packed arena message. The mask /
        pack stages each issue ONE fused operation for the whole arena.
        """
        timer = self.timer
        m, _ = self._arena_coeffs()
        mask_u = any(getattr(c, "arena_mask_momentum", False)
                     for c in self.corrections)
        need_u = self.uses_momentum_buffer and bool(m or mask_u)

        def _mask():
            gidx = arena.communicated_indices(group, selected)
            v = arena.mask_arena(v2d, gidx)
            u = (arena.mask_arena(u2d, gidx)
                 if (mask_u and need_u) else u2d)
            return v, u

        timer.count("dispatch_mask")
        v2d_m, u2d_m = timer.stage("mask", _mask)

        v_views = arena.scatter(group, v2d_m)
        u_views = arena.scatter(group, u2d_m) if need_u else {}
        for slot, st in zip(group.slots, slot_states):
            shape = leaves_p[slot.leaf].shape
            st = st._replace(residual=v_views[slot.leaf].reshape(shape)
                             .astype(self.residual_dtype))
            if need_u:
                st = st._replace(momentum=u_views[slot.leaf].reshape(shape))
            new_states[slot.leaf] = st

        timer.count("dispatch_pack")
        return timer.stage("pack",
                           lambda: arena.pack_group(group, selected))

    def update(self, grads: Any, state: Any, params: Any, lr: jax.Array,
               *, density: float | None = None) -> tuple[Any, Any]:
        """One synchronized step. Returns (new_params, new_state).

        The dispatch ORDER is owned by the configured ``Schedule``
        (``core.overlap``): ``sequential`` runs compress-all → one
        transfer → apply (the historical order, reproduced below by the
        stage helpers it calls), ``chunked`` pipelines per-chunk
        compress+transfer dispatches, ``stale1`` communicates the
        previous step's buffer.
        """
        density = self.density if density is None else density
        return self.schedule.step(self, grads, state, params, lr, density)

    # -- schedule stage helpers (the pipeline's unit operations) ------------

    def _context(self, grads: Any, leaf_state: Any, params: Any):
        """Flatten the step's trees and run tree-level corrections (e.g.
        DGC local clipping — its N^{-1/2} norm is GLOBAL over the whole
        gradient tree, so it must run before any chunking).

        Returns BOTH the raw and the corrected gradient leaves: plans
        (``_plan`` / ``_chunk_plans`` / ``_pending_zeros``) must be built
        from the RAW leaves so §5.5 byte-size dispatch keeps seeing the
        parameter's true storage dtype (a correction like local_clip
        upcasts bf16 leaves to f32 — the mis-dispatch PR 1/PR 4 pinned
        out), while the compute stages consume the corrected leaves.
        """
        leaves_raw, treedef = jax.tree.flatten(grads)
        leaves_p = treedef.flatten_up_to(params)
        leaves_s = treedef.flatten_up_to(leaf_state)
        n_workers = self.transport.num_workers()
        leaves_g = list(leaves_raw)
        for c in self.corrections:
            leaves_g = c.on_grads(leaves_g, leaves_p, n_workers)
        return treedef, leaves_raw, leaves_g, leaves_p, leaves_s, n_workers

    def _compress_plan(self, plan: _StepPlan, leaves_g: list,
                       leaves_p: list, leaves_s: list, new_states: list
                       ) -> tuple[list[jax.Array], list[tuple]]:
        """Residual update + selection + message packing for every sparse
        unit of ``plan`` (arena groups first, then per-leaf fallbacks).

        Each stage body routes through the StageTimer hook
        (core.instrument): a free passthrough under jit/NullTimer, a
        barriered wall-clock sample per stage when bench_transport runs
        the pipeline eagerly (the measured Fig 10 decomposition).
        ``dispatch_<stage>`` counters record fused-operation launches:
        one per leaf in the fallback loop, one per arena for mask/pack —
        and ONE per step for select: all arenas' segments search
        together in a single ``multi_select`` (one count launch per
        iteration for everything). Returns ``(messages, msg_meta)``;
        mutates ``new_states`` in place.
        """
        timer = self.timer
        messages: list[jax.Array] = []
        msg_meta: list[tuple] = []

        if plan.groups:
            accs = [self._accumulate_group(group, comp, leaves_g,
                                           leaves_p, leaves_s)
                    for group, comp in zip(plan.groups, plan.group_comps)]
            timer.count("dispatch_select")
            results = timer.stage(
                "select", lambda: self._select_groups(
                    plan.groups, plan.group_comps, accs))
            for (group, comp), (sel, slot_states), acc in zip(
                    zip(plan.groups, plan.group_comps), results, accs):
                messages.append(self._finish_group(
                    group, comp, sel, slot_states, acc[0], acc[1],
                    leaves_p, new_states))
                msg_meta.append(("arena", group, comp))

        for i, comp, k in plan.sparse:
            timer.count("dispatch_accumulate")
            st = timer.stage("accumulate", lambda i=i: self._accumulate(
                leaves_g[i], leaves_p[i], leaves_s[i]))
            flat_v = st.residual.reshape(-1).astype(jnp.float32)
            timer.count("dispatch_select")
            selected, st = timer.stage(
                "select", lambda f=flat_v, st=st: comp.compress(f, k, st))

            def _mask(st=st, sel=selected):
                st2 = mask_communicated(st, sel.indices, momentum=False)
                for c in self.corrections:
                    st2 = c.on_communicated(st2, sel.indices)
                return st2
            timer.count("dispatch_mask")
            new_states[i] = timer.stage("mask", _mask)
            timer.count("dispatch_pack")
            messages.append(timer.stage(
                "pack",
                lambda sel=selected: self.transport.pack(sel, comp.quantized)))
            msg_meta.append(("leaf", i, comp, k))

        return messages, msg_meta

    def _gather(self, messages: list[jax.Array]) -> list[jax.Array]:
        """Dispatch the transport collective for one message batch."""
        return self.timer.stage(
            "transfer", lambda: self.transport.allgather(messages))

    def _apply_gathered(self, gathered: list[jax.Array],
                        msg_meta: list[tuple], leaves_p: list,
                        new_params: list, lr: jax.Array,
                        n_workers: int) -> None:
        """Decompress gathered messages and apply the SGD update (mutates
        ``new_params`` in place)."""
        timer = self.timer

        def _apply(buf, i, comp, k):
            g_sum = comp.decompress(buf, leaves_p[i].size, k)
            upd = (g_sum / n_workers).reshape(leaves_p[i].shape)
            return (leaves_p[i].astype(jnp.float32)
                    - lr * upd).astype(leaves_p[i].dtype)

        for buf, meta in zip(gathered, msg_meta):
            if meta[0] == "arena":
                _, group, comp = meta
                slot_bufs = arena.split_message(group, buf)
                for slot, sbuf in zip(group.slots, slot_bufs):
                    new_params[slot.leaf] = timer.stage(
                        "unpack", lambda b=sbuf, s=slot: _apply(
                            b, s.leaf, comp, s.k))
            else:
                _, i, comp, k = meta
                new_params[i] = timer.stage(
                    "unpack", lambda b=buf, i=i, c=comp, k=k: _apply(
                        b, i, c, k))

    def _dense_reduce(self, i: int, leaves_g: list) -> jax.Array:
        """Dispatch one dense leaf's allreduce-mean collective."""
        return self.timer.stage(
            "transfer",
            lambda: self.transport.allreduce_mean(leaves_g[i]))

    def _dense_apply(self, i: int, g_mean: jax.Array, leaves_p: list,
                     leaves_s: list, new_states: list, new_params: list,
                     lr: jax.Array) -> None:
        """Momentum-SGD apply of one dense leaf's reduced gradient."""
        st = leaves_s[i]
        if self.weight_decay:
            g_mean = g_mean + self.weight_decay * \
                leaves_p[i].astype(jnp.float32)
        if self.momentum:
            u = self.momentum * st.momentum + g_mean
            upd = (g_mean + self.momentum * u) if self.nesterov else u
            new_states[i] = st._replace(momentum=u)
        else:
            upd = g_mean
        new_params[i] = (leaves_p[i].astype(jnp.float32)
                         - lr * upd).astype(leaves_p[i].dtype)


def build_gradient_sync(
    optimizer: str = "rgc",
    *,
    transport: str = "fused_allgather",
    sync_axes: tuple[str, ...] = (),
    density: float = 0.001,
    momentum: float = 0.9,
    nesterov: bool = False,
    weight_decay: float = 0.0,
    local_clip: float | None = None,
    residual_dtype: Any = jnp.float32,
    no_quant_paths: tuple[str, ...] = ("lm_head", "embed"),
    dense_threshold_bytes: int | None = None,
    trimmed_threshold_bytes: int | None = None,
    warmup_steps_per_stage: int = 0,
    dense_warmup: bool = False,
    bucket_bytes: int | None = None,
    intra_axis: str | None = None,
    fuse_leaves: bool = True,
    fuse_accumulate: bool = False,
    schedule: str = "sequential",
    timer: Any = None,
    **compressor_params: Any,
) -> GradientSync:
    """Build a ``GradientSync`` from string-addressable component names.

    ``optimizer`` may prefix ``+``-joined correction names (see
    ``repro.core.correction``) ahead of a base spec, e.g.
    ``"momentum+clip(threshold_bsearch)"`` or ``"warmup(rgc)"``; a
    corrections-only spec defaults the base to ``"rgc"``. Base resolution:
      * ``"rgc"`` / ``"rgc_quant"`` — the paper's size-based dispatch
        (quantized variant wraps each non-dense compressor per §5.2.3);
      * ``"dense"`` — every leaf dense allreduce (baseline);
      * any registered compressor spec — fixed dispatch through it.

    Spec-named corrections are ADDITIVE: the ``momentum`` / ``local_clip``
    config fields remain the on/off switches for their corrections (legacy
    semantics — so ``"warmup(rgc)"`` keeps momentum correction exactly as
    ``"rgc"`` had it), and naming a correction already implied by a field
    just fixes its position in the pipeline. Ablate by zeroing the field,
    not by omitting the name.

    Transport knobs: ``bucket_bytes`` (bucketed_allgather's per-collective
    byte budget) and ``intra_axis`` (hierarchical's intra-node mesh axis;
    default = last sync axis) are forwarded to the transport factory;
    factories ignore knobs they don't consume. ``timer`` is the
    ``StageTimer`` hook shared by the sync loop and the transport
    (``None`` -> ``NullTimer``).

    ``schedule`` names the §5.6 overlap scheduler (``core.overlap``,
    registry kind ``SCHEDULE``): ``"sequential"`` (default, full-tree
    barrier), ``"chunked"`` (reverse-parameter-order chunk pipelining,
    bitwise identical results, chunk byte budget = ``bucket_bytes``), or
    ``"stale1"`` (one-step-delayed double-buffered sync; its state wraps
    the LeafState tree in an ``overlap.ScheduleState``).

    ``fuse_leaves`` (default on) enables the flat residual arenas: the
    select/mask/pack stages run once per same-dtype arena instead of once
    per leaf, bitwise identical to the per-leaf path
    (``repro.core.arena``). ``fuse_accumulate`` additionally fuses
    residual accumulation into one arena pass (the single-launch
    residual-update+stats kernel) at the cost of possible <= 1 ulp
    momentum-product drift vs the per-leaf graph (XLA FMA contraction).
    ``compressor_params`` may carry ``backend`` ("jnp" | "pallas") for
    the selection kernels; the Pallas backend auto-detects
    compiled-vs-interpreted per platform.
    """
    corr_names, base = split_corrections(optimizer)
    optimizer = base or "rgc"
    corrections: tuple[Correction, ...] | None = None
    if corr_names:
        corrections = tuple(
            registry.make(registry.CORRECTION, name,
                          momentum=momentum, nesterov=nesterov,
                          local_clip=local_clip, density=density,
                          warmup_steps_per_stage=warmup_steps_per_stage,
                          dense_warmup=dense_warmup, **compressor_params)
            for name in corr_names)

    policy_kw = {}
    if dense_threshold_bytes is not None:
        policy_kw["dense_threshold_bytes"] = dense_threshold_bytes
    if trimmed_threshold_bytes is not None:
        policy_kw["trimmed_threshold_bytes"] = trimmed_threshold_bytes

    quantize = False
    if optimizer in ("rgc", "rgc_quant"):
        policy: DispatchPolicy = registry.make(
            registry.DISPATCH_POLICY, "size_based", **policy_kw)
        quantize = optimizer == "rgc_quant"
    elif optimizer == "dense":
        policy = FixedPolicy("dense")
    elif registry.contains(registry.COMPRESSOR, optimizer):
        # fail at build time, not at the first jitted step, for specs that
        # parse but cannot be constructed (e.g. "quantized(dense)")
        registry.make(registry.COMPRESSOR, optimizer, **compressor_params)
        policy = FixedPolicy(optimizer)
    else:
        raise ValueError(
            f"unknown optimizer {optimizer!r}: expected rgc | rgc_quant | "
            f"dense | a registered compressor "
            f"{registry.names(registry.COMPRESSOR)}, optionally prefixed "
            f"by '+'-joined corrections "
            f"{registry.names(registry.CORRECTION)}")

    timer = timer if timer is not None else NullTimer()
    transport_kw: dict[str, Any] = {"sync_axes": tuple(sync_axes),
                                    "timer": timer}
    if bucket_bytes is not None:
        transport_kw["bucket_bytes"] = bucket_bytes
    if intra_axis is not None:
        transport_kw["intra_axis"] = intra_axis

    return GradientSync(
        policy=policy,
        transport=registry.make(registry.TRANSPORT, transport,
                                **transport_kw),
        density=density,
        momentum=momentum,
        nesterov=nesterov,
        weight_decay=weight_decay,
        local_clip=local_clip,
        quantize=quantize,
        no_quant_paths=tuple(no_quant_paths),
        residual_dtype=residual_dtype,
        fuse_leaves=fuse_leaves,
        fuse_accumulate=fuse_accumulate,
        schedule=make_schedule(schedule),
        chunk_bytes=(DEFAULT_BUCKET_BYTES if bucket_bytes is None
                     else int(bucket_bytes)),
        corrections=corrections,
        compressor_params=dict(compressor_params),
        timer=timer,
    )
