"""The correctness check of the on-chip benchmark, rehearsed on the CPU
at the configurations' smoke sizes: a sound run passes; each fault a
cell can have, planted under the timed path, and the control precision
put in the program's place, fail it."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))


def rehearse(*args) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "_rehearse_prog.py"), *args],
        capture_output=True, text=True, env=env, timeout=600, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", ["lstm-ptb-rgc", "internlm2-4k-rgc"])
def test_sound_run_is_correct(workload):
    out = rehearse(workload, "run")
    assert out["correct"], out


@pytest.mark.parametrize("workload,fault", [
    ("lstm-ptb-rgc", "unchanged"),
    ("lstm-ptb-rgc", "half_batch"),
    ("internlm2-4k-rgc", "unchanged"),
    ("internlm2-4k-rgc", "half_batch"),
    ("lstm-ptb-rgc-x4", "no_exchange"),
])
def test_planted_fault_is_not_correct(workload, fault):
    out = rehearse(workload, "run", fault)
    assert not out["correct"], out


@pytest.mark.parametrize("workload", ["lstm-ptb-rgc", "internlm2-4k-rgc"])
def test_control_precision_is_not_correct(workload):
    out = rehearse(workload, "control")
    assert not out["correct"], out
