"""The trace reduction of the on-chip benchmark on a small synthetic
trace whose numbers are worked out by hand."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import tracing  # noqa: E402

MS = 1_000_000  # ns


def chip(n, modules, ops):
    return {"name": f"/device:TPU:{n}",
            "lines": {"XLA Modules": modules, "XLA Ops": ops}}


def host(events):
    return {"name": "/host:CPU", "lines": {"python": events}}


def planes():
    # chip 0: two steps of jit_step (0-10 ms, 14-24 ms), one layer call
    # of jit_bench_select (30-33 ms) and a small program inside the window
    steps0 = [("jit_step(7)", 0 * MS, 10 * MS), ("jit_step(7)", 14 * MS, 10 * MS),
              ("jit_convert(2)", 11 * MS, 1 * MS),
              ("jit_bench_select(3)", 30 * MS, 3 * MS)]
    ops0 = [("fusion.1", 0 * MS, 6 * MS), ("%all-gather.2 = s32[4,1,264]{1,0} all-gather(s32[1,264]{1,0} %p)",
             5 * MS, 3 * MS),
            ("fusion.1", 14 * MS, 6 * MS), ("all-reduce.5", 21 * MS, 2 * MS),
            ("copy.9", 11 * MS, 1 * MS), ("fusion.8", 30 * MS, 3 * MS)]
    # chip 1: same steps, busy 8 + 8 ms, no collectives
    steps1 = [("jit_step(7)", 0 * MS, 10 * MS), ("jit_step(7)", 14 * MS, 10 * MS),
              ("jit_bench_select(3)", 30 * MS, 5 * MS)]
    ops1 = [("fusion.1", 1 * MS, 8 * MS), ("fusion.1", 15 * MS, 8 * MS)]
    hostev = [("bench_steps", 0, 25 * MS), ("next_batch", 10 * MS, 3 * MS),
              ("on_metrics", 8 * MS, 6 * MS)]
    return [host(hostev), chip(0, steps0, ops0), chip(1, steps1, ops1),
            {"name": "/device:TPU:1 SparseCore", "lines": {}}]


def test_merge_unions_overlaps():
    assert tracing.merge([(5, 8), (0, 6), (10, 12), (12, 13), (3, 3)]) == [
        (0, 8), (10, 13)]


def test_op_name_keeps_name_type_and_opcode():
    assert tracing.op_name("%fusion.5 = f32[8]{0} fusion(f32[8]{0} %a)") == (
        "fusion.5 f32[8]{0}", "fusion")
    assert tracing.op_name("copy.9") == ("copy.9", "copy.9")


def test_module_name_strips_the_run_id():
    assert tracing.module_name("jit_bench_fwd_bwd(123)") == "jit_bench_fwd_bwd"


def test_reduce_by_hand():
    red = tracing.reduce(planes())
    assert red["chips"] == 2
    # window: span of the dominant program (jit_step) on each chip: 24 ms
    assert red["window_s"] == pytest.approx(24e-3)
    # chip 0 busy: [0,8) + [11,12) + [14,20) + [21,23) = 17 ms;
    # chip 1 busy: [1,9) + [15,23) = 16 ms; mean 16.5 ms
    assert red["busy_s"] == pytest.approx(16.5e-3)
    # collectives on chip 0 only: 3 + 2 ms, averaged over 2 chips
    assert red["collective_s"] == pytest.approx(2.5e-3)
    assert red["step_programs"] == 2
    # the step's device time: two 10 ms executions on each chip
    assert red["step_s"] == pytest.approx(20e-3)
    # the layer call: 3 ms and 5 ms, one call each
    assert red["bench"]["jit_bench_select"]["s"] == pytest.approx(4e-3)
    assert red["bench"]["jit_bench_select"]["calls"] == 1
    ops = dict(red["device_ops"])
    assert ops["fusion.1"] == pytest.approx((12 + 16) / 2 * 1e-3)
    assert "fusion.8" not in ops          # outside the steps' window
    gaps = dict(red["idle_gaps"])
    # chip 0 gaps: [8,11) mid 9.5 -> on_metrics (shortest covering);
    # [12,14) mid 13 -> next_batch; [20,21) mid 20.5 -> bench_steps.
    # chip 1 gaps: [9,15) mid 12 -> next_batch. Averaged over 2 chips.
    assert gaps["on_metrics"] == pytest.approx(1.5e-3)
    assert gaps["next_batch"] == pytest.approx(4e-3)
    assert gaps["bench_steps"] == pytest.approx(0.5e-3)


def test_reduce_without_a_chip_reads_nothing():
    assert tracing.reduce([host([("x", 0, 1)])]) is None


def test_per_call_seconds():
    rec = {"trace": {"bench": {"jit_bench_select": {"s": 0.006, "calls": 3}}}}
    assert tracing.per_call_s(rec, "jit_bench_select") == pytest.approx(0.002)
    assert tracing.per_call_s(rec, "jit_bench_fwd_bwd") is None
    assert tracing.per_call_s({}, "jit_bench_select") is None
