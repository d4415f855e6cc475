"""Reduction of a profiler trace to the benchmark's device numbers.

A trace is read into plain data: a list of planes, each
``{"name": str, "lines": {line name: [(event name, start_ns, dur_ns)]}}``
(``load``). A TPU chip is a plane ``/device:TPU:<n>``; its ``XLA Modules``
line holds one event per program execution and its ``XLA Ops`` line one
event per operation. The host's Python thread is a line of the
``/host:CPU`` plane.

``reduce`` splits each chip's events in two:

* programs whose name starts with ``jit_bench_`` are the layer calls the
  benchmark makes on their own: their device time and call count per
  program name;
* of the others, the program that holds the chip longest is the
  training step. Its device time is ``step_s``, the span of its
  executions on the chip the traced window; busy time is the union of the operation intervals
  in it, idle gaps are what the union leaves, and each of the longest
  ``LABELLED`` gaps is labelled by the innermost host event in progress at
  its midpoint (the rest count as "shorter gaps").

Numbers are averaged over the chips that ran.
"""
from __future__ import annotations

import re
from collections import defaultdict

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)")
HLO = re.compile(r"^%?([\w.-]+) = (.*?) ([a-z][\w-]*)\(")
BENCH_PREFIX = "jit_bench_"
LABELLED = 200          # longest idle gaps per chip labelled by host event


def load(path: str) -> list[dict]:
    """A ``.xplane.pb`` file as plain planes."""
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events)
        planes.append({"name": plane.name, "lines": lines})
    return planes


def merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Union of [start, end) intervals as disjoint sorted intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def op_name(name: str) -> tuple[str, str]:
    """An XLA op event's ``(short name, opcode)``: ``%fusion.5 = f32[8]
    fusion(...)`` -> ``("fusion.5 f32[8]", "fusion")``; a bare name is
    its own opcode."""
    m = HLO.match(name)
    if not m:
        bare = name.lstrip("%")
        return bare, bare
    kind = m.group(2)
    return f"{m.group(1)} {kind[:60]}", m.group(3)


def module_name(name: str) -> str:
    """``jit_step(123)`` -> ``jit_step``."""
    return name.split("(")[0].strip()


def _host_events(planes: list[dict]) -> list[tuple[str, float, float]]:
    evs = []
    for plane in planes:
        if plane["name"].startswith("/host:"):
            for line, events in plane["lines"].items():
                if line.startswith("python") or line.startswith("Python"):
                    evs.extend(events)
    return evs


class GapLabeller:
    """Labels an idle gap by the innermost host event (the shortest) in
    progress at its midpoint; "unknown" where none is."""

    def __init__(self, host: list[tuple[str, float, float]]):
        self.names = [n for n, _, _ in host]
        iv = np.array([(s, s + d, d) for _, s, d in host], np.float64)
        self.iv = iv.reshape(-1, 3)

    def __call__(self, start: float, end: float) -> str:
        mid = (start + end) / 2
        hit = np.nonzero((self.iv[:, 0] <= mid) & (self.iv[:, 1] >= mid))[0]
        if not hit.size:
            return "unknown"
        return self.names[hit[np.argmin(self.iv[hit, 2])]]


def reduce(planes: list[dict], top: int = 10) -> dict | None:
    """Device numbers of a trace; None when no chip ran anything."""
    chips = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    chips = [p for p in chips if p["lines"].get("XLA Modules")]
    if not chips:
        return None
    label = GapLabeller(_host_events(planes))
    n = len(chips)
    busy = window = collective = step_time = 0.0
    op_time: dict[str, float] = defaultdict(float)
    gaps: list[tuple[float, str]] = []
    bench: dict[str, list[float]] = defaultdict(lambda: [0.0, 0])
    steps = 0
    for chip in chips:
        mods = chip["lines"]["XLA Modules"]
        other: dict[str, list[tuple[float, float]]] = defaultdict(list)
        for name, s, d in mods:
            base = module_name(name)
            if base.startswith(BENCH_PREFIX):
                bench[base][0] += d / n
                bench[base][1] += 1 / n
            else:
                other[base].append((s, s + d))
        if not other:
            continue
        # the training step is the program that holds the chip longest
        step_mods = max(other.values(),
                        key=lambda iv: sum(e - s for s, e in iv))
        lo = min(s for s, _ in step_mods)
        hi = max(e for _, e in step_mods)
        ops = [(name, s, d) for name, s, d in chip["lines"].get("XLA Ops", [])
               if s < hi and s + d > lo]
        spans = ([(max(s, lo), min(s + d, hi)) for _, s, d in ops]
                 if ops else step_mods)
        union = merge(spans)
        step_time += sum(e - s for s, e in step_mods) / n
        busy += sum(e - s for s, e in union) / n
        window += (hi - lo) / n
        steps = max(steps, len(step_mods))
        for name, s, d in ops:
            short, opcode = op_name(name)
            op_time[short] += d / n
            if COLLECTIVE.match(opcode):
                collective += d / n
        idle = sorted(((s1 - e0, e0, s1) for (_, e0), (s1, _)
                       in zip(union, union[1:])), reverse=True)
        gaps += [(g, label(a, b)) for g, a, b in idle[:LABELLED]]
        gaps += [(g, "shorter gaps") for g, _, _ in idle[LABELLED:]]
    gap_time: dict[str, float] = defaultdict(float)
    for g, label in gaps:
        gap_time[label] += g / n
    ns = 1e-9
    return {
        "chips": n,
        "busy_s": busy * ns,
        "window_s": window * ns,
        "step_programs": steps,
        "step_s": step_time * ns,
        "collective_s": collective * ns,
        "bench": {k: {"s": v[0] * ns, "calls": round(v[1])}
                  for k, v in sorted(bench.items())},
        "device_ops": [[k, v * ns] for k, v in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v * ns] for k, v in sorted(
            gap_time.items(), key=lambda kv: -kv[1])[:top]],
    }


def per_call_s(rec: dict, program: str) -> float | None:
    """Device seconds per call of one of the benchmark's own programs in
    a run record's reduced trace; None when the trace has none."""
    entry = ((rec.get("trace") or {}).get("bench") or {}).get(program)
    if not entry or not entry["calls"] or entry["s"] <= 0:
        return None
    return entry["s"] / entry["calls"]
