"""The on-chip benchmark's per-metric readers and FLOP counts, against
values worked out by hand, and the benchmark's files against
``BENCHMARK.json``."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path.insert(0, BENCH)

from chipbench import harness  # noqa: E402
from chipref import lstm, transformer  # noqa: E402

REC = {
    "setup_s": 12.5, "compile_s": 4.0, "window_s": 2.0, "tokens": 7000,
    "peak_bytes": 3_000_000_000,
    "chips": 1, "flops_per_token": 3e8,
    "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    "select_bytes": 819e6,
    "trace": {"bench": {"jit_bench_fwd_bwd": {"s": 0.02, "calls": 2},
                        "jit_bench_select": {"s": 0.01, "calls": 5}},
              "collective_s": 0.004, "step_programs": 2, "step_s": 0.03},
}

EXPECTED = {
    "setup_s": 12.5,
    "compile_s": 4.0,
    "tokens_per_s": 3500.0,
    "peak_hbm_gb": 3.0,
    # 3e8 FLOP/token * 3500 tokens/s over 197e12 FLOP/s
    "mfu": 100 * 3e8 * 3500 / 197e12,
    "fwd_bwd_ms": 10.0,
    # 15 ms a step less 10 ms of fwd/bwd
    "sync_ms": 5.0,
    # least time 819e6 B / 819e9 B/s = 1 ms over 2 ms a call
    "select_roofline": 50.0,
}


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_by_hand(name):
    assert harness.reader(name)(REC) == pytest.approx(EXPECTED[name])


def test_collective_ms_only_across_chips():
    read = harness.reader("collective_ms")
    assert read(REC) is None
    assert read({**REC, "chips": 4}) == pytest.approx(2.0)


@pytest.mark.parametrize("name", ["fwd_bwd_ms", "sync_ms", "select_roofline",
                                  "mfu", "tokens_per_s",
                                  "peak_hbm_gb", "compile_s"])
def test_reader_finds_nothing(name):
    assert harness.reader(name)({"chips": 1, "peaks": None}) is None


def test_sync_ms_needs_the_steps_and_the_fwd_bwd_call():
    read = harness.reader("sync_ms")
    no_call = {**REC, "trace": {**REC["trace"], "bench": {}}}
    no_steps = {**REC, "trace": {**REC["trace"], "step_programs": 0}}
    assert read(no_call) is None
    assert read(no_steps) is None


def test_cell_whose_entry_is_not_listed_finds_its_files():
    entry = {"name": "internlm2-4k-rgc", "config": "internlm2-1.8b-cut",
             "traffic": "zipf-4x4096", "chips": 1}
    cell = harness.load_cell(entry["name"], entry=entry)
    assert cell.family == "transformer"
    assert cell.config["num_hidden_layers"] == 2
    assert cell.traffic["seq"] == 4096


def test_lstm_flops_per_token_by_hand():
    cfg = {"vocab_size": 512, "embedding_size": 64, "hidden_size": 96,
           "num_layers": 2}
    # LSTM MACs (64+96)*384 + (96+96)*384 = 135168; head 96*512*15/16
    assert lstm.flops_per_token(cfg, 16) == pytest.approx(
        6 * (135168 + 46080))


def test_transformer_flops_per_token_by_hand():
    cfg = {"vocab_size": 512, "hidden_size": 128, "intermediate_size": 256,
           "num_hidden_layers": 2, "num_attention_heads": 4,
           "num_key_value_heads": 2}
    # per layer: q,o 2*128*128 + k,v 2*128*64 + mlp 3*128*256 = 147456,
    # attention 2*4*32*33/2 = 4224; head 128*512*31/32 = 63488
    assert transformer.flops_per_token(cfg, 32) == pytest.approx(
        6 * (2 * (147456 + 4224) + 63488))


def test_every_metric_has_a_reader_and_every_cell_its_files():
    b = bench()
    for m in b["end_to_end"] + b["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py")), m["name"]
    configs = {c["name"]: c for c in b["configs"]}
    for w in b["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.chips == w["chips"]
        conf = configs[w["config"]]
        with open(os.path.join(ROOT, conf["file"])) as f:
            assert json.load(f)["reduced"] == conf["reduced"]
        assert set(cell.job["limits"]) == {"loss_gap", "loss1_gap",
                                           "grad_gap", "change_gap"}
