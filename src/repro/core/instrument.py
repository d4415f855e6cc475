"""Stage instrumentation for the sync pipeline (Fig 10's decomposition).

``GradientSync.update`` and the transports thread every pipeline stage
through a ``StageTimer`` hook so the paper's Fig 10 time decomposition
can be measured on the REAL pipeline instead of an artificial loop. The
stage set refines Fig 10's by one split: the paper's ``mask`` bar merges
residual/momentum accumulation with post-selection state masking — two
different memory passes — so we time them separately as ``accumulate``
(Alg 4 l.8-19: weight decay + momentum correction + residual add) and
``mask`` (Alg 4 l.21-23: clearing V/U at communicated coordinates).
Summing the two recovers the paper's ``mask`` bar. The rest match Fig
10: ``select`` (communication-set selection), ``pack`` (wire-format
packing), ``transfer`` (the collectives, sparse and dense), ``unpack``
(scatter-add decompression + parameter apply).

Alongside wall time, ``GradientSync`` counts ``dispatch_<stage>`` —
fused-operation launches per stage (one per LEAF on the per-leaf path,
one per ARENA with ``fuse_leaves``) — and the transports count
``collectives`` / ``messages``; these are the O(leaves) → O(arenas)
facts ``benchmarks/bench_transport.py`` asserts on.

Both implementations run each stage's thunk under
``jax.named_scope(<stage>)``, so the stages are named in the compiled
step: every instruction a stage emits carries it in its ``op_name``
(``jit(step)/sync/select/...``), and so does each op event of a profiler
trace of that step. The trainer adds ``fwd_bwd`` (the model's loss and
gradients) and ``sync`` (``GradientSync.update``) around them, and
``kernels.segmented.multi_select`` adds ``search`` (one survivor count of
the bisection loop), ``compact`` (the one exact compaction of every
bsearch slot) and ``fallback`` (a trimmed slot's exact top-k after its
buckets overflow) inside ``select``. Every schedule and transport names
its stages through this hook. To read the stages' device time, trace a
few steps on the chip and reduce the trace by scope::

    with jax.profiler.trace(trace_dir):
        state = trainer.run(state, batches, 5)

then ``chipbench.scopes.reduce`` (``benchmarks/chip``) on the planes that
``chipbench.tracing.load`` reads from ``trace_dir`` and the step's
``compiled.as_text()``. A scope is metadata only: the compiled program
is otherwise the same.

* ``NullTimer`` — the default everywhere. ``stage`` calls the thunk
  under the stage's scope; safe (and free) under ``jit``/``shard_map``
  tracing.
* ``WallClockTimer`` — wraps each stage with a ``jax.block_until_ready``
  barrier and accumulates wall time per stage. Only meaningful for EAGER
  (op-by-op) execution: under ``jit`` the thunk runs once at trace time
  and the barrier is a no-op on tracers, so times would be trace times.
  ``benchmarks/bench_transport.py`` runs the pipeline eagerly with this
  timer and emits ``BENCH_transport.json``.

Counters (``count``) record dimensionless stage facts — e.g. the
bucketed transport's collective count per step — without a barrier.

``set_lane`` opens a named attribution lane: while a lane is set, stage
times are ADDITIONALLY accumulated under it (``summary()["lanes"]``).
The ``chunked`` overlap schedule (core.overlap) sets one lane per
pipeline chunk, giving the per-chunk Fig 10 decomposition
``benchmarks/bench_transport.py``'s ``measured_overlap`` section
reports.
"""
from __future__ import annotations

import time
from collections import defaultdict
from typing import Any, Callable

import jax

# Canonical stage order of one sync step (Fig 10's x-axis, with the
# paper's "mask" bar split into accumulate + mask — sum them to compare
# against Fig 10 directly). Pinned by tests/test_transport.py.
STAGES = ("accumulate", "select", "mask", "pack", "transfer", "unpack")


class NullTimer:
    """No-op timer: zero overhead, trace-safe. The default hook."""

    active = False

    def stage(self, name: str, thunk: Callable[[], Any]) -> Any:
        with jax.named_scope(name):
            return thunk()

    def count(self, name: str, n: int = 1) -> None:
        pass

    def set_lane(self, lane: str | None) -> None:
        pass

    def summary(self) -> dict:
        return {}


class WallClockTimer:
    """Per-stage wall-clock accumulator with device barriers (eager only)."""

    active = True

    def __init__(self) -> None:
        self.times: dict[str, list[float]] = defaultdict(list)
        self.counts: dict[str, int] = defaultdict(int)
        # per-lane stage attribution (the chunked schedule's per-chunk
        # lanes): {lane: {stage: total_s}} accumulated alongside the
        # unlaned totals above
        self.lane_times: dict[str, dict[str, float]] = defaultdict(
            lambda: defaultdict(float))
        self._lane: str | None = None

    def stage(self, name: str, thunk: Callable[[], Any]) -> Any:
        t0 = time.perf_counter()
        with jax.named_scope(name):
            out = thunk()
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        self.times[name].append(dt)
        if self._lane is not None:
            self.lane_times[self._lane][name] += dt
        return out

    def count(self, name: str, n: int = 1) -> None:
        # coerce: callers may pass numpy/DeviceArray values — keep the
        # counter a python int
        self.counts[name] += int(n)

    def set_lane(self, lane: str | None) -> None:
        self._lane = lane

    def reset(self) -> None:
        self.times.clear()
        self.counts.clear()
        self.lane_times.clear()
        self._lane = None

    def summary(self) -> dict:
        """Per-stage totals/means plus the share of the summed stage time.

        ``{"stages": {name: {calls, total_s, mean_ms, share}},
           "counts": {...}, "total_s": float}``; stage order follows
        ``STAGES`` with any custom stage names appended. When lanes were
        set (``set_lane``), a ``"lanes"`` key additionally maps each
        lane to its per-stage second totals.
        """
        totals = {n: sum(ts) for n, ts in self.times.items()}
        grand = sum(totals.values())
        order = [s for s in STAGES if s in totals] + sorted(
            n for n in totals if n not in STAGES)
        stages = {}
        for n in order:
            ts = self.times[n]
            stages[n] = {
                "calls": len(ts),
                "total_s": totals[n],
                "mean_ms": 1e3 * totals[n] / max(len(ts), 1),
                "share": totals[n] / grand if grand > 0 else 0.0,
            }
        out = {"stages": stages, "counts": dict(self.counts),
               "total_s": grand}
        if self.lane_times:
            out["lanes"] = {lane: dict(stages_)
                            for lane, stages_ in self.lane_times.items()}
        return out
