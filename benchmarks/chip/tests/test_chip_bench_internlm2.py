"""The ``internlm2-4l-4k-rgc`` cell on the CPU at its configuration's
smoke sizes: the program against the plain reference (``chipref``) on
seeded weights is correct under the cell's limits, and the planted
faults and the fp8 control are not; its configuration against the
repository's own InternLM2-1.8B; its ``fwd_bwd_mfu`` reader by hand."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

from chipbench import harness  # noqa: E402
from test_chip_bench_check import rehearse  # noqa: E402

CELL = "internlm2-4l-4k-rgc"


def test_sound_run_is_correct():
    out = rehearse(CELL, "run")
    assert out["correct"], out


@pytest.mark.parametrize("mode", [("run", "unchanged"),
                                  ("run", "half_batch"),
                                  ("control",)],
                         ids=["unchanged", "half_batch", "control"])
def test_fault_or_control_is_not_correct(mode):
    out = rehearse(CELL, *mode)
    assert not out["correct"], out


def test_configuration_keeps_the_published_widths():
    from repro.configs.internlm2_1_8b import FULL
    cfg = harness.load_cell(CELL).config
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["intermediate_size"]) == (
        FULL.d_model, FULL.num_heads, FULL.num_kv_heads, FULL.d_ff)
    assert cfg["hidden_size"] // cfg["num_attention_heads"] == FULL.head_dim
    assert cfg["rope_theta"] == FULL.rope_theta
    assert cfg["tie_word_embeddings"] == FULL.tie_embeddings
    assert cfg["published"] == {"num_hidden_layers": FULL.num_layers,
                                "vocab_size": FULL.vocab_size}
    # one stage of six, a quarter of the vocabulary
    assert FULL.num_layers % cfg["num_hidden_layers"] == 0
    assert cfg["num_hidden_layers"] >= 4
    assert cfg["vocab_size"] * 4 == FULL.vocab_size


FWD_BWD = {"chips": 1, "tokens_per_step": 16384, "flops_per_token": 2e9,
           "peaks": {"bf16_flops_per_s": 197e12},
           "trace": {"bench": {"jit_bench_fwd_bwd": {"s": 1.0, "calls": 2}}}}


def test_fwd_bwd_mfu_by_hand():
    read = harness.reader("fwd_bwd_mfu")
    # 2e9 FLOP/token * 16384 tokens in 0.5 s a call, over 197e12 FLOP/s
    assert read(FWD_BWD) == pytest.approx(100 * 2e9 * 16384 / 0.5 / 197e12)
    # four chips: each call runs one chip's quarter of the step's tokens
    assert read({**FWD_BWD, "chips": 4}) == pytest.approx(
        read(FWD_BWD) / 4)


@pytest.mark.parametrize("rec", [
    {**FWD_BWD, "trace": {"bench": {"jit_bench_select": {"s": 1.0,
                                                         "calls": 5}}}},
    {**FWD_BWD, "trace": None},
    {**FWD_BWD, "peaks": None},
], ids=["no_fwd_bwd_call", "no_trace", "no_peaks"])
def test_fwd_bwd_mfu_finds_nothing(rec):
    assert harness.reader("fwd_bwd_mfu")(rec) is None
