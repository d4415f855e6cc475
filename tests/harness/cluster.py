"""Simulated-cluster machinery (no jax import at module scope).

The harness splits one host CPU into N XLA devices
(``--xla_force_host_platform_device_count``), builds a pure data-parallel
mesh over them — flat ``("data",)`` or, for the hierarchical transport,
2-axis ``("node", "local")`` — and drives real training loops through
``repro.train.trainer.Trainer`` — the trainer's fully-manual shard_map
path. Each worker sees
its own batch shard and computes LOCAL gradients, so the residual /
correction / selection / allgather pipeline is exercised exactly as on a
real cluster (p = N in Eq 1), just without the wire.

Device forcing must happen before jax initializes, so multi-device runs
from an already-jax-initialized process (pytest, benchmarks) go through
``run_cluster`` → ``_cluster_prog.py`` in a subprocess; in-process use
(``train_and_eval``) is for programs that called ``force_host_devices``
first.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Any

TESTS_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(TESTS_DIR)
SRC_DIR = os.path.join(REPO_ROOT, "src")
CLUSTER_PROG = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "_cluster_prog.py")

_FORCE_FLAG = "--xla_force_host_platform_device_count"


def force_host_devices(n: int | None = None) -> None:
    """Split the host platform into ``n`` XLA devices.

    ``n=None`` reads ``REPRO_HARNESS_DEVICES`` (default 8), so existing
    subprocess programs that call ``force_host_devices()`` bare keep the
    historical 8-device cluster while the elastic battery scales the
    same programs to 4 / 16 / 32 devices through the environment.

    Only effective before jax initializes its backends — call it at the
    top of a standalone program, before any jax import.
    """
    if n is None:
        n = int(os.environ.get("REPRO_HARNESS_DEVICES", "8"))
    flags = os.environ.get("XLA_FLAGS", "")
    if _FORCE_FLAG in flags:
        flags = " ".join(f for f in flags.split()
                         if not f.startswith(_FORCE_FLAG))
    os.environ["XLA_FLAGS"] = f"{flags} {_FORCE_FLAG}={n}".strip()


def check(name: str, cond: bool) -> None:
    """Subprocess-program assertion: PASS/FAIL line + nonzero exit."""
    print(("PASS" if cond else "FAIL"), name)
    if not cond:
        sys.exit(1)


def subprocess_env(extra: dict[str, str] | None = None) -> dict[str, str]:
    """Environment for harness/test subprocesses: repo src + tests on
    path, pinned to the CPU platform — they simulate their cluster on
    forced host devices, and an accelerator belongs to the parent."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    path = [SRC_DIR, TESTS_DIR]
    if env.get("PYTHONPATH"):
        path.append(env["PYTHONPATH"])
    env["PYTHONPATH"] = os.pathsep.join(path)
    env.update(extra or {})
    return env


def make_node_mesh(nodes: int = 2, local: int | None = None):
    """2-axis ``("node", "local")`` mesh over the forced host devices —
    the simulated multi-node cluster the ``hierarchical`` transport syncs
    over (inter-node sparse allgather on "node", intra-node dense psum on
    "local"). ``local=None`` uses all remaining devices per node."""
    import jax

    from repro.launch.mesh import _make_mesh
    n = len(jax.devices())
    if local is None:
        if n % nodes:
            raise ValueError(f"{n} devices not divisible by {nodes} nodes")
        local = n // nodes
    return _make_mesh((nodes, local), ("node", "local"))


def train_and_eval(
    arch: str,
    optimizer: str,
    steps: int,
    *,
    transport: str = "fused_allgather",
    schedule: str | None = None,
    bucket_bytes: int | None = None,
    intra_axis: str | None = None,
    fuse_leaves: bool | None = None,
    backend: str | None = None,
    nodes: int | None = None,
    lr: float = 0.1,
    momentum: float = 0.9,
    density: float = 0.01,
    local_clip: float | None = None,
    warmup_steps_per_stage: int = 0,
    dense_warmup: bool = False,
    seed: int = 0,
    batch: int = 8,
    seq_len: int = 64,
    eval_batches: int = 4,
    log_every: int = 0,
    use_mesh: bool = True,
    ckpt_dir: str | None = None,
    ckpt_every: int = 0,
    ckpt_full_every: int = 4,
    resume: bool = False,
    resume_step: int | None = None,
    crash_at: int | None = None,
) -> dict[str, Any]:
    """One real training run on the simulated cluster + held-out loss.

    ``nodes=N`` runs on the 2-axis ``("node","local")`` mesh (N nodes x
    devices/N locals) instead of the flat ``("data",)`` mesh — the
    hierarchical transport's home. ``bucket_bytes`` / ``intra_axis`` /
    ``fuse_leaves`` / ``backend`` / ``schedule`` parameterize the
    transport / flat-arena / selection-kernel / §5.6-overlap-scheduler
    knobs (None = the TrainConfig defaults).

    Returns ``{"held_loss", "losses", "num_devices", "steps", "digest",
    "state_digest", "pending_digest"}``; ``losses`` is the per-step
    training-loss trace (loss is pmean'd over workers inside the step,
    so it is the global-batch loss) and ``digest`` is a sha256 over the
    final params + optimizer-state bytes — equal digests across
    subprocess runs mean BITWISE-identical training (what the arena
    parity tests assert). ``state_digest`` hashes EVERY worker's copy
    (params + residual/velocity/threshold + any pending message ring —
    the crash/resume battery's contract) and ``pending_digest`` hashes
    just the in-flight ring ("" for unbuffered schedules).

    Fault-tolerance knobs: ``ckpt_dir``/``ckpt_every``/``ckpt_full_every``
    thread the Trainer's checkpoint chain; ``resume=True`` restores the
    latest checkpoint (or ``resume_step``) and trains only the
    REMAINING steps of ``steps``
    (batch stream replayed deterministically); ``crash_at=t`` simulates a
    hard kill — ``os._exit(17)`` right after step ``t`` completes,
    BEFORE that step's periodic save (so the latest checkpoint is the
    last multiple of ``ckpt_every`` at or below ``t - 1``).
    """
    import dataclasses
    import hashlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import TrainConfig, get_config
    from repro.data import bigram_batches
    from repro.train.trainer import Trainer

    cfg = get_config(arch, smoke=True)
    tc = TrainConfig(lr=lr, momentum=momentum, optimizer=optimizer,
                     transport=transport, density=density,
                     local_clip=local_clip,
                     warmup_steps_per_stage=warmup_steps_per_stage,
                     dense_warmup=dense_warmup, seed=seed)
    overrides = {k: v for k, v in
                 (("bucket_bytes", bucket_bytes), ("intra_axis", intra_axis),
                  ("fuse_leaves", fuse_leaves), ("backend", backend),
                  ("schedule", schedule))
                 if v is not None}
    if overrides:
        tc = dataclasses.replace(tc, **overrides)
    from repro.launch.mesh import make_data_mesh
    if not use_mesh:
        mesh = None
    elif nodes is not None:
        mesh = make_node_mesh(nodes)
    else:
        mesh = make_data_mesh()
    tr = Trainer(cfg, tc, mesh=mesh, ckpt_dir=ckpt_dir,
                 ckpt_every=ckpt_every, ckpt_full_every=ckpt_full_every)
    if resume:
        state = tr.restore_checkpoint(step=resume_step)
    else:
        state = tr.init_state()
    start = state.step

    losses: list[float] = []

    def on_metrics(step, dens, loss):
        losses.append(loss)
        if crash_at is not None and step == crash_at:
            sys.stdout.flush()
            os._exit(17)          # hard kill: no end-save, no cleanup

    train_src = bigram_batches(cfg.vocab_size, batch, seq_len, seed=seed)
    for _ in range(start):        # deterministic replay past the restore
        next(train_src)
    state = tr.run(state, train_src, steps - start, log_every=log_every,
                   on_metrics=on_metrics)

    # held-out loss: fresh batches from the same chain, past the train span
    src = bigram_batches(cfg.vocab_size, batch, seq_len, seed=seed)
    for _ in range(steps):
        next(src)
    held = 0.0
    for _ in range(eval_batches):
        b = {k: jnp.asarray(v) for k, v in next(src).items()}
        held += float(tr.model.loss(state.params, b))

    digest = hashlib.sha256()
    for leaf in (jax.tree.leaves(state.params) + jax.tree.leaves(state.rgc)):
        digest.update(np.asarray(leaf).tobytes())

    # all-worker digests: every device's buffer row, not just device 0's
    from repro.checkpoint import stack_worker_copies
    from repro.core.overlap import ScheduleState
    state_digest = hashlib.sha256()
    for leaf in jax.tree.leaves(stack_worker_copies(
            {"params": state.params, "rgc": state.rgc})):
        state_digest.update(np.asarray(leaf).tobytes())
    pending_digest = ""
    if isinstance(state.rgc, ScheduleState):
        h = hashlib.sha256()
        for leaf in jax.tree.leaves(stack_worker_copies(state.rgc.pending)):
            h.update(np.asarray(leaf).tobytes())
        pending_digest = h.hexdigest()
    return {
        "held_loss": held / eval_batches,
        "losses": losses,
        "num_devices": len(jax.devices()) if use_mesh else 1,
        "steps": state.step,
        "digest": digest.hexdigest(),
        "state_digest": state_digest.hexdigest(),
        "pending_digest": pending_digest,
    }


def run_cluster(spec: dict[str, Any], *, devices: int = 8,
                timeout: int = 1200) -> dict[str, Any]:
    """Run ``train_and_eval(**spec)`` on ``devices`` forced host devices in
    a subprocess; returns its result dict."""
    proc = subprocess.run(
        [sys.executable, CLUSTER_PROG,
         json.dumps({"devices": devices, "run": spec})],
        capture_output=True, text=True, env=subprocess_env(),
        timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(
            f"cluster run failed ({spec.get('arch')}/"
            f"{spec.get('optimizer')}):\nSTDOUT:\n{proc.stdout[-3000:]}\n"
            f"STDERR:\n{proc.stderr[-3000:]}")
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"no RESULT line in cluster output:\n{proc.stdout}")


def convergence_pair(
    arch: str,
    steps: int = 200,
    *,
    devices: int = 8,
    sparse_optimizer: str = "momentum+clip(threshold_bsearch)",
    density: float = 0.01,
    warmup_steps_per_stage: int = 25,
    dense_warmup: bool = False,
    lr: float = 0.1,
    momentum: float = 0.9,
    local_clip: float = 1.0,
    seed: int = 0,
    timeout: int = 1200,
) -> dict[str, Any]:
    """Sparse-with-corrections vs dense ``psum`` on the same mesh/budget.

    The tier-2 convergence-parity criterion: the corrected sparse run's
    held-out loss lands within tolerance of the dense baseline's. The
    dense baseline gets the SAME local clipping (DGC clips both sides of
    its comparisons; an unclipped baseline would measure the clip, not
    the sparsification).
    """
    common = dict(arch=arch, steps=steps, lr=lr, momentum=momentum,
                  local_clip=local_clip, seed=seed)
    dense = run_cluster(dict(common, optimizer="dense",
                             transport="dense_psum"),
                        devices=devices, timeout=timeout)
    sparse = run_cluster(dict(common, optimizer=sparse_optimizer,
                              density=density, local_clip=local_clip,
                              warmup_steps_per_stage=warmup_steps_per_stage,
                              dense_warmup=dense_warmup),
                         devices=devices, timeout=timeout)
    return {"dense": dense, "sparse": sparse,
            "dense_loss": dense["held_loss"],
            "sparse_loss": sparse["held_loss"]}
