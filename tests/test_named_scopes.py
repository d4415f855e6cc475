"""The names the training step carries into its compiled program and its
profiler trace: device scopes ``fwd_bwd``, ``sync``, the six
``StageTimer`` stages, ``search``, ``compact`` (bsearch slots) and
``fallback`` (trimmed slots), and the model's ``attn``, ``mlp`` and
``loss`` inside ``fwd_bwd``, in every instruction's ``op_name``, and the
host spans of ``Trainer.run``.

Meshes need forced host devices before jax initializes, so those cases
run tests/_scopes_prog.py in a subprocess (``run_prog``)."""
import contextlib
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import TrainConfig, get_config
from repro.core.instrument import STAGES, NullTimer
from repro.data import bigram_batches
from repro.models.registry import get_model
from repro.train.trainer import Trainer, make_gradient_sync, make_train_step

_PROG = os.path.join(os.path.dirname(__file__), "_scopes_prog.py")
TC = TrainConfig(optimizer="momentum+clip(threshold_bsearch)", density=0.01,
                 lr=0.1)


def scopes_in(text: str) -> set[str]:
    """Every op_name component but the last (the primitive)."""
    return {part for name in re.findall(r'op_name="([^"]*)"', text)
            for part in name.split("/")[:-1]}


def smoke_step_text(tc: TrainConfig, arch: str = "paper-lstm") -> str:
    model = get_model(get_config(arch, smoke=True))
    params = jax.eval_shape(model.init_params)
    state = jax.eval_shape(make_gradient_sync(tc, None).init, params)
    step = make_train_step(model, None, None, tc, donate=False)
    return step.lower(params, state, model.train_inputs(2, 16),
                      jax.ShapeDtypeStruct((), jnp.float32)
                      ).compile().as_text()


def test_smoke_step_names_its_stages():
    text = smoke_step_text(TC)
    found = scopes_in(text)
    wanted = {"fwd_bwd", "sync", "search", "compact"} | (
        set(STAGES) - {"transfer"})
    assert wanted <= found, wanted - found
    # the bisection's survivor count sits inside the search loop; the
    # bsearch slots share one compaction and have no per-slot branch
    assert re.search(r'op_name="[^"]*/select/while/body/search/', text)
    assert re.search(r'op_name="[^"]*/select/compact/', text)
    assert "fallback" not in found
    assert not [line for line in text.splitlines()
                if " conditional(" in line and "/select/" in line]


def test_trimmed_step_names_its_fallback():
    """Trimmed top-k slots keep their buckets and the per-slot exact
    fallback, inside a conditional branch under ``select``."""
    text = smoke_step_text(dataclasses.replace(
        TC, optimizer="momentum+clip(trimmed_topk)"))
    found = scopes_in(text)
    assert "fallback" in found and "compact" not in found
    assert re.search(r'op_name="[^"]*/select/cond/[^"]*/fallback/', text)


MODEL_SCOPES = {"internlm2-1.8b": ("attn", "mlp", "loss"),
                "paper-lstm": ("loss",)}


@pytest.mark.parametrize("arch", sorted(MODEL_SCOPES))
def test_smoke_step_names_its_model_parts(arch):
    """The model's parts sit inside ``fwd_bwd`` under their bare names,
    in the forward and in the rematerialised backward of the scanned
    layers and loss chunks."""
    text = smoke_step_text(TC, arch)
    for scope in MODEL_SCOPES[arch]:
        for way in (r"jvp\(\)", r"transpose\(jvp\(\)\)"):
            assert re.search(rf'op_name="jit\(step\)/fwd_bwd/{way}/'
                             rf'[^"]*/{scope}/', text), (scope, way)
    assert {"attn", "mlp", "loss"} & scopes_in(text) == set(
        MODEL_SCOPES[arch])


def _instructions(text: str) -> list[str]:
    return [re.sub(r", metadata=\{[^}]*\}", "", line)
            for line in text.splitlines() if " = " in line]


@pytest.mark.parametrize("arch", sorted(MODEL_SCOPES))
def test_model_scopes_are_metadata_only(arch, monkeypatch):
    """Without the model's scopes the compiled step has the same
    instructions; only their metadata differs."""
    scoped = smoke_step_text(TC, arch)
    real = jax.named_scope
    monkeypatch.setattr(
        jax, "named_scope", lambda name: contextlib.nullcontext()
        if name in MODEL_SCOPES[arch] else real(name))
    bare = smoke_step_text(TC, arch)
    assert scoped != bare
    assert _instructions(scoped) == _instructions(bare)


@pytest.mark.parametrize("case", ["data", "data_model", "fsdp"])
def test_mesh_step_names_its_stages(case, run_prog):
    run_prog(_PROG, case)


def test_stage_hook_scopes_its_thunk():
    def f(x):
        return NullTimer().stage("pack", lambda: x * 2.0)
    text = jax.jit(f).lower(jnp.ones(4)).as_text(debug_info=True)
    assert re.search(r'"jit\(f\)/pack/mul"', text)


def test_trainer_run_writes_host_spans(tmp_path):
    from jax.profiler import ProfileData
    cfg = get_config("paper-lstm", smoke=True)
    tr = Trainer(cfg, TC, ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=2)
    state = tr.init_state()
    feed = bigram_batches(cfg.vocab_size, 2, 16, seed=0)
    state = tr.run(state, feed, 1, log_every=0)      # compile outside
    with jax.profiler.trace(str(tmp_path / "trace")):
        tr.run(state, feed, 2, log_every=0, on_metrics=lambda *a: None)
    (path,) = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path / "trace")
               for f in fs if f.endswith(".xplane.pb")]
    spans: dict[str, list] = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "train_step" or ev.name.startswith("trainer."):
                    spans.setdefault(ev.name, []).append(ev)
    assert len(spans["train_step"]) == 2
    assert sorted(dict(ev.stats)["step_num"] for ev in spans["train_step"]
                  ) == [1, 2]
    for name in ("trainer.feed", "trainer.dispatch", "trainer.metrics"):
        assert len(spans[name]) == 2, name
    # a save on the ckpt_every cadence (after step 2), one at the run's end
    assert len(spans["trainer.checkpoint"]) == 2
