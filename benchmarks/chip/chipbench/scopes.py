"""The training step's trace split by the program's own scopes and spans.

An extension of ``tracing``: it reads the same plain planes
(``tracing.load``) and the same training step (the program that holds the
chip longest), and adds what the program names inside that step.

Device scopes. The program runs each part of its step under a
``jax.named_scope``, which reaches every compiled instruction's
``op_name``: ``fwd_bwd`` (the model's loss and gradients), ``sync`` (the
gradient sync) and, inside it, the six ``StageTimer`` stages
(``accumulate select mask pack transfer unpack``); inside ``select``,
``search`` (one survivor count of the selection's bisection loop) and
``fallback`` (the exact selection a slot falls back to when its buckets
overflow). ``hlo_ops`` maps each instruction of the compiled step
(``compiled.as_text()``) to its opcode and its chain of these scopes, e.g.
``("sync", "select", "fallback")``; an XLA op event of the trace is
matched to it by instruction name.

Host spans. ``Trainer.run`` writes ``train_step`` around each step and
``trainer.feed``, ``trainer.dispatch``, ``trainer.metrics`` and
``trainer.checkpoint`` inside it, on the profiler's clock.

``reduce`` gives, per step and averaged over chips:

* ``scopes``: device ms of each scope chain joined by ``/`` (``sync``,
  ``sync/select``, ``sync/select/search``, ...), as the union of its ops'
  intervals, nested chains included;
* ``scopes_self``: the same without nested chains, plus
  ``unattributed``; these add up to the busy time. Each moment of busy
  time goes to the innermost op then running (the latest started), and an
  op with no scope of its own inside a scoped ``while`` or
  ``conditional`` takes the scope of the op that encloses it. Ops of other
  programs in the window are unattributed;
* ``search_iters``: bisection iterations per step: the executions of
  the most-run outermost ``search`` instruction (each runs once per
  iteration; one that XLA hoists out of the loop runs less often);
* ``fallback_share``: of the ``conditional`` ops under ``select`` (one per
  slot and step), the share that ran a ``fallback`` op;
* ``idle_by_span``: idle device ms labelled by the innermost host span of
  ``SPANS`` at the gap's midpoint (``tracing.GapLabeller``).
"""
from __future__ import annotations

import heapq
import re
from bisect import bisect_left
from collections import defaultdict

from . import tracing

STAGES = ("accumulate", "select", "mask", "pack", "transfer", "unpack")
SCOPES = frozenset(("fwd_bwd", "sync", *STAGES, "search", "fallback"))
SPANS = frozenset(("train_step", "trainer.feed", "trainer.dispatch",
                   "trainer.metrics", "trainer.checkpoint"))
OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')


def chain(op_name: str) -> tuple[str, ...]:
    """The program's scopes in an ``op_name``, outermost first:
    ``jit(step)/sync/select/while/body/search/reduce_sum`` ->
    ``("sync", "select", "search")``. The last component is the
    primitive, never a scope."""
    parts = op_name.split(";")[0].split("/")[:-1]
    return tuple(p for p in parts if p in SCOPES)


def hlo_ops(text: str) -> dict[str, tuple[str, tuple[str, ...]]]:
    """``{instruction name: (opcode, scope chain)}`` of a compiled
    module's text."""
    out = {}
    for line in text.splitlines():
        line = line.strip()
        if line.startswith("ROOT "):
            line = line[5:]
        m = tracing.HLO.match(line)
        if not m:
            continue
        name = OP_NAME.search(line)
        out[m.group(1)] = (m.group(3), chain(name.group(1)) if name else ())
    return out


def _step_modules(mods) -> list[tuple[float, float]]:
    """Executions of the training step: the program, not one of the
    benchmark's own, that holds the chip longest (as ``tracing.reduce``)."""
    other: dict[str, list[tuple[float, float]]] = defaultdict(list)
    for name, s, d in mods:
        base = tracing.module_name(name)
        if not base.startswith(tracing.BENCH_PREFIX):
            other[base].append((s, s + d))
    if not other:
        return []
    return max(other.values(), key=lambda iv: sum(e - s for s, e in iv))


def _chip(chip: dict, ops_map: dict, label) -> dict | None:
    """One chip's numbers, in ns over all its traced steps."""
    steps = sorted(_step_modules(chip["lines"]["XLA Modules"]))
    if not steps:
        return None
    lo, hi = steps[0][0], steps[-1][1]
    starts = [s for s, _ in steps]

    def in_step(s: float) -> bool:
        i = bisect_left(starts, s + 1) - 1
        return i >= 0 and s < steps[i][1]

    evs = []        # (start, end, own chain, opcode, instruction)
    for name, s, d in chip["lines"].get("XLA Ops", []):
        s, e = max(s, lo), min(s + d, hi)
        if e <= s:
            continue
        short, opcode = tracing.op_name(name)
        instr = short.split(" ")[0]
        if in_step(s) and instr in ops_map:
            opcode, own = ops_map[instr]
        else:
            instr, own = None, None     # another program's op
        evs.append((s, e, own, opcode, instr))
    evs.sort(key=lambda ev: (ev[0], -ev[1]))

    # sweep: the innermost running op (latest start, then earliest end)
    # takes each elementary interval of busy time
    eff: list[tuple[str, ...] | None] = [None] * len(evs)
    parent = [-1] * len(evs)
    self_ns: dict[tuple[str, ...] | None, float] = defaultdict(float)
    bounds = sorted({t for s, e, *_ in evs for t in (s, e)})
    heap: list[tuple[float, float, int]] = []
    j = 0
    for t0, t1 in zip(bounds, bounds[1:]):
        while j < len(evs) and evs[j][0] <= t0:
            while heap and heap[0][1] <= t0:
                heapq.heappop(heap)
            up = heap[0][2] if heap else -1
            own = evs[j][2]
            parent[j] = up
            # a step op with no scope takes the scope of its enclosing op
            eff[j] = eff[up] if own == () and up >= 0 else own
            heapq.heappush(heap, (-evs[j][0], evs[j][1], j))
            j += 1
        while heap and heap[0][1] <= t0:
            heapq.heappop(heap)
        if heap:
            self_ns[eff[heap[0][2]]] += t1 - t0

    incl: dict[str, float] = defaultdict(float)
    scoped_self: dict[str, float] = defaultdict(float)
    for c, ns in self_ns.items():
        if not c:
            scoped_self["unattributed"] += ns
            continue
        scoped_self["/".join(c)] += ns
        for i in range(1, len(c) + 1):
            incl["/".join(c[:i])] += ns

    # outermost search ops run once per bisection iteration (those XLA
    # hoists out of the loop run less often): the most-run one counts
    runs: dict[str, int] = defaultdict(int)
    for i, ev in enumerate(evs):
        if ev[2] and "search" in ev[2] and (
                parent[i] < 0 or "search" not in (eff[parent[i]] or ())):
            runs[ev[4]] += 1
    # per-slot conditionals under select, and those that ran a fallback
    conds = {i for i, ev in enumerate(evs)
             if ev[3] == "conditional" and "select" in (eff[i] or ())
             and "fallback" not in eff[i]}
    fell = set()
    for i, ev in enumerate(evs):
        if ev[2] and "fallback" in ev[2]:
            up = parent[i]
            while up >= 0 and up not in conds:
                up = parent[up]
            if up >= 0:
                fell.add(up)

    union = tracing.merge([(s, e) for s, e, *_ in evs])
    idle: dict[str, float] = defaultdict(float)
    for (_, e0), (s1, _) in zip(union, union[1:]):
        idle[label(e0, s1)] += s1 - e0
    return {"steps": len(steps), "scopes": incl, "scopes_self": scoped_self,
            "idle_by_span": idle,
            "search_runs": max(runs.values(), default=0),
            "conds": len(conds), "fell": len(fell)}


def reduce(planes: list[dict], ops_map: dict) -> dict | None:
    """The step's scopes and spans (see the module's docstring); None when
    no chip ran anything. ``ops_map`` is ``hlo_ops`` of the step."""
    chips = [p for p in planes if tracing.DEVICE_PLANE.match(p["name"])
             and p["lines"].get("XLA Modules")]
    label = tracing.GapLabeller([ev for ev in tracing._host_events(planes)
                                 if ev[0] in SPANS])
    per = [r for r in (_chip(c, ops_map, label) for c in chips) if r]
    if not per:
        return None
    out: dict = {}
    for key in ("scopes", "scopes_self", "idle_by_span"):
        acc: dict[str, float] = defaultdict(float)
        for r in per:
            for k, ns in r[key].items():
                acc[k] += ns * 1e-6 / r["steps"] / len(per)
        out[key] = dict(sorted(acc.items(), key=lambda kv: -kv[1]))
    iters = [r["search_runs"] / r["steps"] for r in per if r["search_runs"]]
    shares = [r["fell"] / r["conds"] for r in per if r["conds"]]
    out["search_iters"] = sum(iters) / len(iters) if iters else None
    out["fallback_share"] = sum(shares) / len(shares) if shares else None
    return out


def scope_ms(rec: dict, path: str) -> float | None:
    """Device ms per step of one scope chain in a run record's reduced
    trace; None when the trace carries no scopes."""
    return ((rec.get("trace") or {}).get("scopes") or {}).get(path)
