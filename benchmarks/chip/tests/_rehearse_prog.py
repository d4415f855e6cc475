"""Drive one rehearsal of a cell on the CPU at its smoke sizes, past the
harness's look for a chip, and print the check's verdict as JSON.

    python _rehearse_prog.py <workload> run [<fault>]
    python _rehearse_prog.py <workload> control

``run`` drives a whole run (set-up, a short window, the check), with a
fault from ``chipbench.faults`` planted under the timed path if given;
``control`` puts the configuration's control precision in the program's
place.
"""
import json
import os
import sys
import time

T0 = time.perf_counter()
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from chipbench import check, harness  # noqa: E402

# cells whose files are kept ready; BENCHMARK.json has no entry for them
UNLISTED = {
    "lstm-ptb-rgc-x4": {"name": "lstm-ptb-rgc-x4", "config": "paper-lstm",
                        "traffic": "ptb-20x35", "chips": 4},
    "internlm2-4k-rgc": {"name": "internlm2-4k-rgc",
                         "config": "internlm2-1.8b-cut",
                         "traffic": "zipf-4x4096", "chips": 1},
}

workload, mode = sys.argv[1], sys.argv[2]
cell = harness.load_cell(workload, rehearse=True,
                         entry=UNLISTED.get(workload))
harness.prepare(cell.chips, True)
if mode == "run":
    fault = sys.argv[3] if len(sys.argv) > 3 else None
    out = harness.run(cell, 2**31 + 11, 0.3, False, T0, rehearse=True,
                      fault=fault)
    gaps = {k: v["value"] for k, v in out["check"].items()}
    correct = out["correct"]
else:
    import calibrate
    job = harness.Job(cell, rehearse=True)
    gaps = calibrate.stand_in_gaps(job, 2**31 + 11,
                                   control=cell.config["control"])
    correct = check.verdict(gaps, cell.job["limits"])
print(json.dumps({"correct": correct, "gaps": gaps}))
