#!/usr/bin/env python3
"""Readings that the correctness limits of a cell are set from.

    python3 benchmarks/chip/calibrate.py --workload lstm-ptb-rgc \
        --seeds 1,2,3 --stand-in-seeds 4,5,6 [--rehearse]

In one process, with one compiled step:

* ``program``: for each of ``--seeds``, the trainer's first three steps
  from that seed against the plain reference (the readings a sound run
  gives; their largest is a number's lower reading);
* ``control``: for each of ``--stand-in-seeds``, the reference computed
  in the configuration's control precision (``control`` in its file),
  put in the program's place;
* ``half`` and, on several chips, ``no_exchange``: the reference with
  that fault planted, put in the program's place.

Each reading is one JSON line on standard output: ``{"kind", "seed",
"loss_gap", "grad_gap", "change_gap"}`` (a program reading adds the
three steps' losses of both sides); a summary line follows.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def program_gaps(job, seed: int) -> dict:
    from chipbench import check, traffic
    pool = job.batches(seed)
    losses: list[float] = []
    state, prog, _ = job.first_steps(
        seed, pool, traffic.cycle(pool),
        lambda step, d, loss: losses.append(loss), losses)
    del state
    ref = job.reference(seed, pool)
    return {**check.gaps(prog, ref), "losses": prog.losses,
            "ref_losses": ref.losses}


def stand_in_gaps(job, seed: int, control: str | None = None,
                  fault: str | None = None) -> dict:
    from chipbench import check
    pool = job.batches(seed)
    ref = job.reference(seed, pool)
    stand_in = job.reference(seed, pool, control=control, fault=fault)
    return {**check.gaps(stand_in, ref), "losses": stand_in.losses,
            "ref_losses": ref.losses}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--stand-in-seeds", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    from chipbench import check, harness
    cell = harness.load_cell(args.workload, rehearse=args.rehearse)
    harness.prepare(cell.chips, args.rehearse)
    job = harness.Job(cell, args.rehearse)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    stand_in = [int(s) for s in args.stand_in_seeds.split(",") if s]
    rows = []

    def emit(kind, seed, gaps):
        row = {"kind": kind, "seed": seed, **gaps,
               "t": round(time.perf_counter() - T0, 1)}
        rows.append(row)
        print(json.dumps(row), flush=True)

    for seed in seeds:
        emit("program", seed, program_gaps(job, seed))
    job.trainer = None
    kinds = [("control", cell.config["control"], None), ("half", None, "half")]
    if cell.chips > 1:
        kinds.append(("no_exchange", None, "no_exchange"))
    for seed in stand_in:
        for kind, control, fault in kinds:
            emit(kind, seed, stand_in_gaps(job, seed, control, fault))
    summary = {}
    for kind in {r["kind"] for r in rows}:
        mine = [r for r in rows if r["kind"] == kind]
        agg = max if kind == "program" else min
        summary[kind] = {k: agg(r[k] for r in mine) for k in check.NUMBERS}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
