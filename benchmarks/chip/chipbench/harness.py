"""One run of one cell: set-up, measured window, traced window, check.

A cell is an entry of ``workloads`` in ``BENCHMARK.json``. Its pieces are
found by name: ``jobs/<workload>.json`` (mesh, ``TrainConfig`` fields,
the check's limits), the configuration file that ``configs`` names,
``traffic/<traffic>.json`` and, per metric, ``metrics/<metric>.py``
whose ``read(record)`` turns the run's record into the number (or None
when the run has nothing for it to read).

The run, in order:

1. set-up: weights from the seed on the device in one jitted call, the
   trainer's step compiled (or loaded from the persistent cache), and its
   first three steps driven through ``Trainer.run`` on the window's
   feed; the program's readings of those steps are taken;
2. the window: ``Trainer.run`` one step at a time until ``seconds`` have
   passed; the per-step hook that forces each step's loss to the host
   stamps each step's end;
3. the peak device memory;
4. with ``trace``: a profiled stretch of steps and the layers' calls on
   their own (``layers``), reduced by ``tracing``;
5. the program's state is dropped and the plain reference follows the
   same three steps (``check``).
"""
from __future__ import annotations

import contextlib
import gc
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".chipbench_trace")
CHECK_STEPS = 3


class NoChip(RuntimeError):
    pass


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    job: dict

    @property
    def family(self) -> str:
        return self.config["family"]


def benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def load_cell(name: str, rehearse: bool = False,
              entry: dict | None = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, or the one ``entry``
    describes in the same form (a cell whose files are ready before its
    entry is; its configuration is ``configs/<config>.json`` where
    ``BENCHMARK.json`` does not list it)."""
    bench = benchmark()
    if entry is None:
        entry = next((w for w in bench["workloads"] if w["name"] == name),
                     None)
    if entry is None:
        raise SystemExit(f"unknown workload {name!r}")
    conf = next((c for c in bench["configs"] if c["name"] == entry["config"]),
                {"file": os.path.relpath(os.path.join(
                    BENCH, "configs", entry["config"] + ".json"), ROOT)})
    cfg = load_json(os.path.join(ROOT, conf["file"]))
    traffic = load_json(os.path.join(BENCH, "traffic",
                                     entry["traffic"] + ".json"))
    job = load_json(os.path.join(BENCH, "jobs", name + ".json"))
    if rehearse:
        cfg = {**cfg, **cfg.get("smoke", {})}
        traffic = {**traffic, **traffic.get("smoke", {})}
    return Cell(name, int(entry["chips"]), cfg, traffic, job)


def metric_entries(name: str, trace: bool) -> list[dict]:
    """The cell's metrics: end-to-end ones untraced, per-layer traced."""
    bench = benchmark()
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if name in m.get("workloads", [name])]


def reader(metric: str):
    path = os.path.join(BENCH, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def prepare(chips: int, rehearse: bool) -> None:
    """Environment for the run; must come before JAX is imported."""
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    # libtpu would log under a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if chips > 1:
            flag = f"--xla_force_host_platform_device_count={chips}"
            os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                                       + flag).strip()
        return
    # only files inside the checkout outlast a run: keep the compile
    # cache there unless the environment already points inside it
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    inside = env and os.path.abspath(env).startswith(ROOT + os.sep)
    if not inside:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR


def require_chips(chips: int) -> list:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoChip(f"needs {chips} TPU chip(s); JAX sees "
                     f"{len(devices)} {devices[0].platform} device(s)")
    return devices[:chips]


def peaks(kind: str) -> dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} has no peaks in peaks.json")
    return table[kind]


def _peak_bytes(devices) -> int | None:
    vals = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            vals.append(int(stats["peak_bytes_in_use"]))
    return max(vals) if vals else None


class Job:
    """What a cell's runs share: the trainer (and its compiled step), the
    references and the settings. Built once per process."""

    def __init__(self, cell: Cell, rehearse: bool = False):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.configs.base import TrainConfig
        from repro.launch.mesh import mesh_from_spec
        from repro.train.trainer import Trainer

        from chipref import rgc as rgc_ref

        if rehearse:
            self.devices = jax.devices()[:cell.chips]
        else:
            from repro.launch.cache import use_compile_cache
            use_compile_cache()
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              0)
            self.devices = require_chips(cell.chips)
        self.cell = cell
        self.fam = importlib.import_module(f"chipref.{cell.family}")
        adapter = importlib.import_module(
            f"chipbench.adapters.{cell.family}")
        job, cfg = cell.job, cell.config
        self.mesh = mesh_from_spec(job["mesh"]) if cell.chips > 1 else None
        self.tc = TrainConfig(**job["train"])
        self.trainer = Trainer(adapter.model_config(cfg), self.tc,
                               mesh=self.mesh)
        self.dtype = jnp.dtype(cfg["dtype"])
        self.specs = self.fam.weight_specs(cfg)
        self.rep = (NamedSharding(self.mesh, P())
                    if self.mesh is not None else None)
        self.seq = cell.traffic["seq"]
        self.global_batch = cell.traffic["batch_per_chip"] * cell.chips
        self.density = self.trainer.density_at(0)
        self.settings = rgc_ref.Settings(
            density=float(self.tc.density), lr=float(self.tc.lr),
            momentum=float(self.tc.momentum),
            clip_norm=(1.0 if self.tc.local_clip is None
                       else float(self.tc.local_clip)))

    def initial_params(self, seed: int):
        from chipref import weights
        return weights.make(self.specs, self.dtype, seed, self.rep)

    def batches(self, seed: int) -> list[dict]:
        from . import traffic
        return traffic.pool(self.cell.traffic, self.cell.config["vocab_size"],
                            self.cell.chips, seed)

    def first_steps(self, seed: int, pool: list[dict], feed, on_metrics,
                    losses: list):
        """The trainer's state after its first steps from the seed, the
        program's readings of them, and the host seconds of
        ``.lower().compile()`` of the trainer's step (a load from the
        persistent cache once one run has compiled it). Every run takes
        that same path, so that the cache holds the step under one key."""
        import jax.numpy as jnp

        from repro.train.trainer import TrainState

        from . import check
        tr, lr = self.trainer, self.tc.lr
        params = self.initial_params(seed)
        state = TrainState(params, tr._sync.init(params), 0)
        del params
        compile_s = None
        step_fn = tr._step_fn(self.density)
        if hasattr(step_fn, "lower"):       # a planted fault has none
            b0 = {k: jnp.asarray(v) for k, v in pool[0].items()}
            t = time.perf_counter()
            step_fn.lower(state.params, state.rgc, b0,
                          jnp.float32(lr)).compile()
            compile_s = time.perf_counter() - t
        prog = check.Readings()
        s1 = tr.run(state, feed, 1, log_every=0, on_metrics=on_metrics)
        del state
        prog.grad = check.program_grad(self.initial_params(seed), s1, lr)
        state = tr.run(s1, feed, CHECK_STEPS - 1, log_every=0,
                       on_metrics=on_metrics)
        del s1
        prog.change = check.program_change(self.initial_params(seed),
                                           state.params)
        prog.losses = list(losses[-CHECK_STEPS:])
        return state, prog, compile_s

    def reference(self, seed: int, pool: list[dict], control: str | None =
                  None, fault: str | None = None):
        """The plain reference's readings of the first steps; with
        ``control`` in that lower precision, with ``fault`` planted."""
        import jax

        from chipref import numerics
        from chipref import rgc as rgc_ref

        from . import check
        nx = (numerics.REFERENCE if control is None
              else numerics.CONTROLS[control])
        p0 = check.copies(self.initial_params(seed))[0]
        with jax.default_matmul_precision("highest"):
            return check.reference_run(
                self.fam, rgc_ref, self.cell.config, p0, pool[:CHECK_STEPS],
                self.cell.chips, self.settings, nx,
                self.cell.job.get("reference_rows", self.global_batch),
                fault=fault,
                params_dtype=nx.dtype if nx.params else None)


def run(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
        rehearse: bool = False, fault: str | None = None) -> dict:
    """One run; returns the result object (see ``run.py``)."""
    import numpy as np

    from . import check, faults, traffic

    job = Job(cell, rehearse)
    pool = job.batches(seed)
    feed = traffic.cycle(pool)
    stamps: list[float] = []
    losses: list[float] = []

    def on_metrics(step, dens, loss):
        stamps.append(time.perf_counter())
        losses.append(loss)

    rec: dict = {"chips": cell.chips,
                 "tokens_per_step": job.global_batch * job.seq,
                 "flops_per_token": job.fam.flops_per_token(cell.config,
                                                            job.seq)}
    with contextlib.ExitStack() as stack:
        if fault:
            stack.enter_context(faults.plant(fault, job.trainer))
        state, prog, rec["compile_s"] = job.first_steps(
            seed, pool, feed, on_metrics, losses)
        gc.collect()

        # the measured window
        stamps.clear()
        losses.clear()
        t_start = time.perf_counter()
        rec["setup_s"] = t_start - t0
        while time.perf_counter() - t_start < seconds:
            state = job.trainer.run(state, feed, 1, log_every=0,
                                    on_metrics=on_metrics)
        rec["window_s"] = stamps[-1] - t_start
        rec["step_s"] = list(np.diff([t_start] + stamps))
        rec["steps"] = len(stamps)
        rec["tokens"] = len(stamps) * job.global_batch * job.seq
        failed = sum(not math.isfinite(x) for x in losses)
        rec["peak_bytes"] = _peak_bytes(job.devices)

        t_window = time.perf_counter()
        traced = {}
        if trace:
            states = [state]
            del state
            rec.update(_traced(job, states, feed, on_metrics, traced))
        else:
            del state
        del feed
        job.trainer = None
        gc.collect()

    # the plain reference, once the program's state is gone
    t_ref = time.perf_counter()
    gaps = check.gaps(prog, job.reference(seed, pool))
    timing = {"setup_s": rec["setup_s"], "window_s": rec["window_s"],
              "step_min_s": min(rec["step_s"]),
              "step_max_s": max(rec["step_s"]),
              "traced_s": t_ref - t_window, **traced,
              "reference_s": time.perf_counter() - t_ref}
    limits = cell.job["limits"]
    compared = check.compared(limits)
    correct = check.verdict(gaps, limits)

    kind = job.devices[0].device_kind
    rec["peaks"] = None if rehearse else peaks(kind)
    metrics = {}
    for m in metric_entries(cell.name, trace):
        val = reader(m["name"])(rec)
        if val is not None:
            metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    device = {"platform": job.devices[0].platform, "kind": kind,
              "count": len(job.devices),
              "memory_peak_bytes": rec["peak_bytes"]}
    out = {"correct": bool(correct), "attempted": rec["steps"],
           "failed": failed, "metrics": metrics, "device": device}
    if trace and rec.get("trace"):
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
        out["breakdown"] = {"device_ops": rec["trace"]["device_ops"],
                            "idle_gaps": rec["trace"]["idle_gaps"]}
    out["timing"] = timing
    out["not_compared"] = {k: gaps[k] for k in check.NUMBERS
                           if k not in compared}
    out["check"] = {k: {"value": gaps[k], "limit": limits[k]}
                    for k in compared}
    return out


def _layer_calls(job: Job, state, feed):
    from . import layers
    return layers.LayerCalls(job.trainer, state, next(feed),
                             job.cell.traffic["batch_per_chip"],
                             job.density)


def _traced(job: Job, states: list, feed, on_metrics,
            timing: dict) -> dict:
    """Profile a stretch of steps, then the layers' own calls; the host
    seconds of each part go into ``timing`` (those of the two profiled
    parts include reading their trace back, ``trace_load_s``).

    ``states`` holds the window's last state and is emptied, so that
    only one training state is alive while the layers are called."""
    import jax

    from . import tracing
    cell, trainer = job.cell, job.trainer
    steps = int(cell.job.get("trace_steps", 2))
    rounds = int(cell.job.get("layer_rounds", 1))
    state = states.pop()
    t = time.perf_counter()
    with _profile() as steps_trace:
        for _ in range(steps):
            state = trainer.run(state, feed, 1, log_every=0,
                                on_metrics=on_metrics)
        jax.block_until_ready(state.params)
    timing["trace_steps_s"] = time.perf_counter() - t
    t = time.perf_counter()
    calls = _layer_calls(job, state, feed)
    del state
    calls.warm()
    timing["layer_warm_s"] = time.perf_counter() - t
    t = time.perf_counter()
    with _profile() as layers_trace:
        n_calls = calls.run(rounds)
    timing["layer_calls_s"] = time.perf_counter() - t
    out = {"layer_calls": n_calls, "select_bytes": calls.select_bytes,
           "trace_steps": steps}
    del calls
    t = time.perf_counter()
    red = tracing.reduce(steps_trace["planes"])
    lay = tracing.reduce(layers_trace["planes"])
    timing["reduce_s"] = time.perf_counter() - t
    timing["trace_load_s"] = steps_trace["load_s"] + layers_trace["load_s"]
    if red is not None and lay is not None:
        red["bench"] = lay["bench"]
    out["trace"] = red
    return out


@contextlib.contextmanager
def _profile():
    """Trace the enclosed block; the planes land in the yielded dict."""
    import jax

    from . import tracing
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    box: dict = {"planes": []}
    jax.profiler.start_trace(TRACE_DIR)
    try:
        yield box
    finally:
        jax.profiler.stop_trace()
    t = time.perf_counter()
    for dirpath, _, files in os.walk(TRACE_DIR):
        for f in files:
            if f.endswith(".xplane.pb"):
                box["planes"] = tracing.load(os.path.join(dirpath, f))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    box["load_s"] = time.perf_counter() - t
