#!/usr/bin/env python3
"""On-chip benchmark of the RedSync trainer: one run of one cell.

    python3 benchmarks/chip/run.py --workload lstm-ptb-rgc --seed 7 \
        --seconds 30 --trace 0

The cells are the ``workloads`` of ``BENCHMARK.json``; each is a
``Trainer`` job on 1 or 4 TPU chips (``chipbench/harness.py`` describes
a run). The last line of standard output is one JSON object::

    {"correct": ..., "attempted": <steps in the window>, "failed": <steps
     whose loss was not finite>, "metrics": {name: {"value", "unit"}},
     "device": {"platform", "kind", "count", "memory_peak_bytes"[,
     "busy_s", "window_s"]}[, "breakdown": {...}], "timing": {...},
     "not_compared": {...}, "check": {...}}

With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics. ``timing`` splits the run's host
time (set-up, window, traced part, reference). ``not_compared`` holds
the readings that a cell's limits leave out (a limit of null);
``check`` holds each number the correctness check compared, beside its
limit. Both are also the last lines of standard error.

Without the chips the cell asks for, the run exits 3 and prints no
result. ``--rehearse`` runs the same flow on the CPU at the
configuration's smoke sizes and prints a rehearsal line, never the
result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, smoke sizes; prints no result line")
    args = ap.parse_args(argv)

    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    from chipbench import harness
    cell = harness.load_cell(args.workload, rehearse=args.rehearse)
    harness.prepare(cell.chips, args.rehearse)
    try:
        out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                          T0, rehearse=args.rehearse)
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    print("timing " + " ".join(f"{k}={v:.1f}" for k, v in
                                out["timing"].items()), file=sys.stderr)
    for name, v in out["not_compared"].items():
        print(f"reading {name} {v!r} (not compared)", file=sys.stderr)
    for name, c in out["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    if args.rehearse:
        print("rehearsal (CPU, smoke sizes; not a result): "
              + json.dumps(out))
        return 0
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
