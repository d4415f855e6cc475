"""The split of the step's trace by the program's scopes and spans
(``chipbench.scopes``) and its readers, on a small synthetic trace whose
numbers are worked out by hand."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import harness, scopes  # noqa: E402

MS = 1_000_000  # ns

OPS = {   # instruction: (opcode, scope chain) as hlo_ops gives them
    "fusion.1": ("fusion", ("fwd_bwd",)),
    "fusion.2": ("fusion", ("sync", "accumulate")),
    "fusion.6": ("fusion", ("sync", "select", "search")),   # hoisted
    "while.3": ("while", ("sync", "select")),
    "fusion.4": ("fusion", ("sync", "select", "search")),
    "add.5": ("add", ()),
    "cond.7": ("conditional", ("sync", "select")),
    "fusion.8": ("fusion", ("sync", "select", "fallback")),
    "copy.9": ("copy", ()),
    "cond.10": ("conditional", ("sync", "select")),
    "fusion.11": ("fusion", ("sync", "unpack")),
    "copy.12": ("copy", ()),
}

NEW_METRICS = ("step_fwd_bwd_ms", "step_sync_ms", "step_select_ms",
               "select_fallback_ms", "select_fallback_share", "select_iters")


def ev(name, start, end):
    return (name, start * MS, (end - start) * MS)


def planes():
    # step 1 [0,48): fwd/bwd, accumulate, a hoisted search op, the search
    # loop (3 iterations, an unscoped op in each of the first two), a
    # slot conditional that falls back (an unscoped op inside it) and one
    # that does not, an idle gap [41,43), unpack, an unscoped op
    step1 = [
        ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a)", 0, 10),
        ev("fusion.2", 10, 14), ev("fusion.6", 14, 15),
        ev("while.3", 15, 30),
        ev("fusion.4", 16, 19), ev("add.5", 19, 20),
        ev("fusion.4", 21, 24), ev("add.5", 24, 25),
        ev("fusion.4", 26, 29),
        ev("%cond.7 = (s32[4]{0}, f32[4]{0}) conditional(pred[] %p)", 30, 40),
        ev("fusion.8", 31, 37), ev("copy.9", 37, 39),
        ev("cond.10", 40, 41),
        ev("fusion.11", 43, 47), ev("copy.12", 47, 48)]
    # another program at [49,50), whose op shares a name with the step's
    other = [ev("copy.9", 49, 50)]
    # step 2 [52,84): the search loop runs twice, no slot falls back
    step2 = [
        ev("fusion.1", 52, 62), ev("fusion.2", 62, 66), ev("fusion.6", 66, 67),
        ev("while.3", 67, 77),
        ev("fusion.4", 68, 71), ev("add.5", 71, 72), ev("fusion.4", 73, 76),
        ev("cond.7", 77, 78), ev("cond.10", 78, 79),
        ev("fusion.11", 79, 83), ev("copy.12", 83, 84)]
    modules = [ev("jit_step(7)", 0, 48), ev("jit_convert(2)", 49, 50),
               ev("jit_step(7)", 52, 84)]
    chip = {"name": "/device:TPU:0",
            "lines": {"XLA Modules": modules, "XLA Ops": step1 + other + step2}}
    host = {"name": "/host:CPU", "lines": {"python": [
        ev("train_step", 0, 50.5), ev("trainer.metrics", 40, 44),
        ev("PjitFunction(step)", 40, 43),        # not a program span
        ev("train_step", 50.5, 90), ev("trainer.feed", 50.5, 52)]}}
    return [host, chip]


def test_chain_keeps_the_program_scopes_outermost_first():
    assert scopes.chain("jit(step)/sync/select/while/body/search/reduce_sum"
                        ) == ("sync", "select", "search")
    assert scopes.chain("jit(step)/sync/select/cond/branch_1_fun/fallback/"
                        "jit(_where)/select_n") == ("sync", "select",
                                                    "fallback")
    # the primitive is never a scope; a merged name reads its first part
    assert scopes.chain("jit(step)/sync/mask/select") == ("sync", "mask")
    assert scopes.chain("jit(f)/fwd_bwd/jvp()/transpose;jit(f)/sync/x"
                        ) == ("fwd_bwd",)
    assert scopes.chain("reduce_sum") == ()


def test_hlo_ops_reads_names_opcodes_and_scopes():
    text = """HloModule jit_step
%region_0 (a: f32[], b: f32[]) -> f32[] {
  ROOT %add.5 = f32[] add(f32[] %a, f32[] %b)
}
ENTRY %main (p: pred[]) -> (s32[4], f32[4]) {
  %fusion.4 = s32[4]{0} fusion(f32[4]{0} %x), kind=kLoop, calls=%f, metadata={op_name="jit(step)/sync/select/while/body/search/gt" source_file="a.py" source_line=3}
  ROOT %cond.7 = (s32[4]{0}, f32[4]{0}) conditional(pred[] %p), branch_computations={%b0, %b1}, metadata={op_name="jit(step)/sync/select/cond"}
}"""
    assert scopes.hlo_ops(text) == {
        "add.5": ("add", ()),
        "fusion.4": ("fusion", ("sync", "select", "search")),
        "cond.7": ("conditional", ("sync", "select")),
    }


def test_reduce_by_hand():
    red = scopes.reduce(planes(), OPS)
    # per step (2 steps): fwd_bwd 20 ms; accumulate 8; search 9 + 6 in
    # the loop and 2 hoisted; select's own time: the loop's 4 + 3, its
    # unscoped ops 2 + 1, cond.7 2 + 1 and the unscoped op in it 2, cond.10
    # 1 + 1; fallback 6; unpack 8; unattributed copy.12 twice and the
    # other program's op
    self_ms = {"fwd_bwd": 10, "sync/accumulate": 4,
               "sync/select/search": 8.5, "sync/select": 8.5,
               "sync/select/fallback": 3, "sync/unpack": 4,
               "unattributed": 1.5}
    assert red["scopes_self"] == pytest.approx(self_ms)
    # the self times add up to the busy time: 46 + 1 + 32 ms over 2 steps
    assert sum(red["scopes_self"].values()) == pytest.approx(79 / 2)
    assert red["scopes"] == pytest.approx({
        "fwd_bwd": 10, "sync": 28, "sync/accumulate": 4,
        "sync/select": 20, "sync/select/search": 8.5,
        "sync/select/fallback": 3, "sync/unpack": 4})
    # fusion.4 runs 3 + 2 times; the hoisted fusion.6 once a step
    assert red["search_iters"] == pytest.approx(2.5)
    # one of the four slot conditionals ran a fallback op
    assert red["fallback_share"] == pytest.approx(0.25)
    # gaps: [41,43) mid 42 -> trainer.metrics (PjitFunction is no span);
    # [48,49) mid 48.5 -> train_step; [50,52) mid 51 -> trainer.feed
    assert red["idle_by_span"] == pytest.approx(
        {"trainer.metrics": 1.0, "train_step": 0.5, "trainer.feed": 1.0})


def test_reduce_averages_over_chips():
    one = planes()
    two = one + [{**one[1], "name": "/device:TPU:1"}]
    assert scopes.reduce(two, OPS)["scopes"] == pytest.approx(
        scopes.reduce(one, OPS)["scopes"])


def test_readers_by_hand():
    rec = {"trace": scopes.reduce(planes(), OPS)}
    got = {m: harness.reader(m)(rec) for m in NEW_METRICS}
    assert got == pytest.approx({
        "step_fwd_bwd_ms": 10, "step_sync_ms": 28, "step_select_ms": 20,
        "select_fallback_ms": 3, "select_fallback_share": 25,
        "select_iters": 2.5})


def test_no_fallback_reads_zero():
    trace = scopes.reduce(planes(), OPS)
    trace["scopes"].pop("sync/select/fallback")
    assert harness.reader("select_fallback_ms")({"trace": trace}) == 0.0


def test_without_scopes_everything_is_unattributed_and_nothing_read():
    # a step compiled without named scopes: the parent's program
    bare = {k: (op, ()) for k, (op, _) in OPS.items()}
    red = scopes.reduce(planes(), bare)
    assert red["scopes"] == {}
    assert red["scopes_self"] == pytest.approx({"unattributed": 79 / 2})
    assert red["search_iters"] is None and red["fallback_share"] is None
    for rec in ({"trace": red}, {"trace": None}, {}):
        for m in NEW_METRICS:
            assert harness.reader(m)(rec) is None, m


def test_reduce_without_a_chip_reads_nothing():
    assert scopes.reduce([planes()[0]], OPS) is None
