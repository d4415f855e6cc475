"""Distributed RGC trainer (DESIGN.md §4): nested shard_map train step.

Structure of one step on a mesh with batch axes B = ("pod","data") (or
("data",)) and tensor axis "model":

  outer shard_map — manual over B, auto over "model":
      each data replica computes loss + grads on its LOCAL batch shard;
      gradients are LOCAL (un-averaged) — exactly what RGC consumes.
      GSPMD still shards the model axis inside (with_sharding_constraint).
  inner shard_map — manual over "model" (fully manual now):
      every leaf is a raw local shard; ``GradientSync.update`` (built from
      TrainConfig via the compressor/transport registry) runs the paper's
      Algorithm 4/5 per leaf: residual+momentum correction -> selection ->
      pack -> all_gather over B -> scatter-add decompress -> SGD apply.
      Small leaves take the dense psum fallback. With TP, each model-shard
      group compresses its own shard (Eq 1 with M -> M/tp).

``optimizer="dense"`` gives the paper's baseline (allreduce data
parallelism): same structure, density=1.0 sentinel -> every leaf dense.
The optimizer spec may prefix DGC corrections
("momentum+clip(threshold_bsearch)", see repro.core.correction) — they
run inside GradientSync ahead of the compressor; a "warmup" correction
owns the density schedule (Trainer.density_at defers to it).

Pure data-parallel meshes (no "model" axis — the simulated-cluster
harness, tests/harness/) take a single FULLY-manual shard_map over the
batch axes: params replicated, batch sharded, gradients local. No nested
partial-manual region.

Single-device smoke mode (mesh=None): same code path, sync_axes=(), no
shard_map — used by CPU tests; the RGC algebra is identical with p=1.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.profiler import StepTraceAnnotation, TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelConfig, TrainConfig
from repro.core.gradient_sync import GradientSync, build_gradient_sync
from repro.core.rgc import RGCConfig
from repro.core.schedule import DensitySchedule
from repro.models.common import param_specs
from repro.models.registry import Model, get_model


@dataclass
class TrainState:
    params: Any
    rgc: Any                 # LeafState tree
    step: int = 0


def _batch_axes(mesh: Optional[Mesh]) -> tuple[str, ...]:
    if mesh is None:
        return ()
    return tuple(a for a in mesh.axis_names if a != "model")


def _residual_dtype(tc: TrainConfig):
    return jnp.bfloat16 if tc.residual_dtype == "bf16" else jnp.float32


def make_rgc_config(tc: TrainConfig, mesh: Optional[Mesh]) -> RGCConfig:
    """Legacy RGCConfig view of a TrainConfig (kept for dryrun callers)."""
    quant = tc.optimizer == "rgc_quant"
    return RGCConfig(
        density=tc.density,
        momentum=tc.momentum,
        nesterov=tc.nesterov,
        weight_decay=tc.weight_decay,
        quantize=quant,
        local_clip=tc.local_clip,
        sync_axes=_batch_axes(mesh),
        fuse_messages=tc.transport != "per_leaf_allgather",
        residual_dtype=_residual_dtype(tc),
    )


def make_gradient_sync(tc: TrainConfig, mesh: Optional[Mesh],
                       timer: Any = None) -> GradientSync:
    """Build the composed sync transform a TrainConfig describes.

    ``tc.optimizer`` may be "rgc" / "rgc_quant" / "dense" or any
    registered compressor spec (e.g. "threshold_bsearch",
    "quantized(trimmed_topk)") — see repro.core.registry.
    ``tc.transport`` picks the collective backend; ``tc.bucket_bytes`` /
    ``tc.intra_axis`` parameterize the bucketed / hierarchical backends.
    ``tc.schedule`` picks the §5.6 overlap scheduler (sequential /
    chunked / stale1 — repro.core.overlap). ``timer`` threads a
    StageTimer hook through the pipeline (eager benchmark runs); None =
    free NullTimer.
    """
    return build_gradient_sync(
        tc.optimizer,
        transport=tc.transport,
        sync_axes=_batch_axes(mesh),
        density=tc.density,
        momentum=tc.momentum,
        nesterov=tc.nesterov,
        weight_decay=tc.weight_decay,
        local_clip=tc.local_clip,
        residual_dtype=_residual_dtype(tc),
        warmup_steps_per_stage=tc.warmup_steps_per_stage,
        dense_warmup=tc.dense_warmup,
        bucket_bytes=tc.bucket_bytes,
        intra_axis=tc.intra_axis,
        fuse_leaves=tc.fuse_leaves,
        fuse_accumulate=tc.fuse_accumulate,
        schedule=tc.schedule,
        backend=tc.backend,
        timer=timer,
    )


def _leaf_state_specs(pspec: P, momentum: bool = True) -> Any:
    """LeafState specs congruent with a param's spec (scalars replicated)."""
    from repro.core.residual import LeafState
    return LeafState(pspec, pspec if momentum else P(), P(), P(), P())


def _fwd_bwd(model: Model, params, batch):
    """Loss and local gradients, under the ``fwd_bwd`` scope."""
    with jax.named_scope("fwd_bwd"):
        return jax.value_and_grad(model.loss)(params, batch)


def _sync_update(sync: GradientSync, grads, rgc_state, params, lr, dens):
    """``GradientSync.update``, under the ``sync`` scope."""
    with jax.named_scope("sync"):
        return sync.update(grads, rgc_state, params, lr, density=dens)


def make_train_step(
    model: Model,
    mesh: Optional[Mesh],
    pc: ParallelConfig,
    tc: TrainConfig,
    *,
    density: Optional[float] = None,
    donate: bool = True,
) -> Callable:
    """Build the jitted train step: (params, rgc_state, batch, lr) ->
    (loss, new_params, new_rgc_state)."""
    cfg = model.cfg
    pc = pc or ParallelConfig()
    sync = make_gradient_sync(tc, mesh)
    dens = tc.density if density is None else density
    if tc.optimizer == "dense":
        dens = 1.0
    defs = model.param_defs()

    if mesh is None:
        def step(params, rgc_state, batch, lr):
            loss, grads = _fwd_bwd(model, params, batch)
            new_params, new_state = _sync_update(
                sync, grads, rgc_state, params, lr, dens)
            return loss, new_params, new_state
        return jax.jit(step, donate_argnums=(0, 1) if donate else ())

    baxes = _batch_axes(mesh)

    if "model" not in mesh.axis_names:
        # Pure data-parallel mesh (the simulated-cluster harness): one
        # FULLY-manual shard_map over the batch axes — params replicated,
        # batch sharded, gradients local — with no nested partial-manual
        # region (same pattern as the test_distributed "oracle" case).
        bspec = P(baxes)
        batch_struct = model.train_inputs(1, 1)   # keys only
        batch_specs = {k: bspec for k in batch_struct}

        def flat_step(params, rgc_state, batch, lr):
            loss, grads = _fwd_bwd(model, params, batch)
            new_params, new_state = _sync_update(
                sync, grads, rgc_state, params, lr, dens)
            return jax.lax.pmean(loss, baxes), new_params, new_state

        stepped = jax.shard_map(
            flat_step, mesh=mesh, axis_names=set(baxes),
            in_specs=(P(), P(), batch_specs, P()),
            out_specs=(P(), P(), P()),
            check_vma=False,
        )
        rep = NamedSharding(mesh, P())
        shardings_b = {k: NamedSharding(mesh, bspec) for k in batch_struct}
        return jax.jit(
            stepped,
            in_shardings=(rep, rep, shardings_b, rep),
            out_shardings=(rep, rep, rep),
            donate_argnums=(0, 1) if donate else (),
        )

    pspecs = param_specs(defs, pc, mesh)
    sspecs = jax.tree.map(
        lambda s: _leaf_state_specs(s, sync.uses_momentum_buffer), pspecs,
        is_leaf=lambda x: isinstance(x, P))
    # a double-buffered schedule (stale1) wraps the LeafState tree with
    # its pending message buffers — replicate those (prefix P() spec)
    wrap = getattr(sync.schedule, "wrap_state_specs", None)
    if wrap is not None:
        sspecs = wrap(sspecs, P())
    bspec = P(baxes)     # shard dim 0 over all batch axes

    def inner_sync(grads, params, rgc_state, lr):
        return _sync_update(sync, grads, rgc_state, params, lr, dens)

    def outer(params, rgc_state, batch, lr):
        loss, grads = _fwd_bwd(model, params, batch)
        new_params, new_state = jax.shard_map(
            inner_sync,
            axis_names={"model"},
            in_specs=(pspecs, pspecs, sspecs, P()),
            out_specs=(pspecs, sspecs),
            check_vma=False,
        )(grads, params, rgc_state, lr)
        return jax.lax.pmean(loss, baxes), new_params, new_state

    batch_struct = model.train_inputs(1, 1)   # keys only
    batch_specs = {k: bspec for k in batch_struct}

    # In the outer shard_map only batch axes are manual; params / state / lr
    # are replicated across them (P() prefix specs); the model axis stays
    # auto (GSPMD) — model sharding rides on the array shardings.
    stepped = jax.shard_map(
        outer, mesh=mesh, axis_names=set(baxes),
        in_specs=(P(), P(), batch_specs, P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )

    def build(params, rgc_state, batch, lr):
        return stepped(params, rgc_state, batch, lr)

    shardings_p = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                               is_leaf=lambda x: isinstance(x, P))
    shardings_s = jax.tree.map(lambda s: NamedSharding(mesh, s), sspecs,
                               is_leaf=lambda x: isinstance(x, P))
    shardings_b = {k: NamedSharding(mesh, bspec) for k in batch_struct}
    jitted = jax.jit(
        build,
        in_shardings=(shardings_p, shardings_s, shardings_b,
                      NamedSharding(mesh, P())),
        out_shardings=(NamedSharding(mesh, P()), shardings_p, shardings_s),
        donate_argnums=(0, 1) if donate else (),
    )
    return jitted


def fsdp_parallel_config(pc: ParallelConfig, mesh: Mesh) -> ParallelConfig:
    """FSDP extension of a ParallelConfig: the d_model ("embed") dimension
    additionally shards over the batch axes, so parameters and optimizer
    state are fully sharded over the whole mesh (GSPMD inserts the
    all-gather / reduce-scatter pair)."""
    baxes = _batch_axes(mesh)
    fsdp_axis = baxes if len(baxes) > 1 else baxes[0]
    return pc.with_rule("embed", fsdp_axis)


def make_fsdp_dense_step(model: Model, mesh: Mesh, pc: ParallelConfig,
                         tc: TrainConfig, *, donate: bool = True) -> Callable:
    """Dense GSPMD/FSDP baseline step for models whose replicated residual
    state exceeds HBM (DESIGN.md §Arch-applicability: grok-1-314b).

    Pure pjit: params + momentum sharded over (batch axes x model); XLA
    auto-inserts the reduce-scatter/all-gather schedule; the optimizer is
    plain momentum SGD. RGC structurally does not apply to fully-sharded
    storage (no replicated parameter copy to sparsify against) — this IS
    the recorded finding, not a missing feature.

    Returns (loss, new_params, new_momentum); momentum state is a plain
    f32 param-shaped tree.
    """
    cfg = model.cfg
    defs = model.param_defs()
    fpc = fsdp_parallel_config(pc, mesh)
    pspecs = param_specs(defs, fpc, mesh)
    baxes = _batch_axes(mesh)

    def step(params, momentum, batch, lr):
        from repro.models.common import pure_gspmd
        with pure_gspmd():
            loss, grads = _fwd_bwd(model, params, batch)
        new_m = jax.tree.map(
            lambda m, g: tc.momentum * m + g.astype(jnp.float32),
            momentum, grads)
        upd = new_m
        if tc.nesterov:
            upd = jax.tree.map(
                lambda g, m: g.astype(jnp.float32) + tc.momentum * m,
                grads, new_m)
        new_p = jax.tree.map(
            lambda p, u: (p.astype(jnp.float32) - lr * u).astype(p.dtype),
            params, upd)
        return loss, new_p, new_m

    shard_p = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                           is_leaf=lambda x: isinstance(x, P))
    batch_struct = model.train_inputs(1, 1)
    shard_b = {k: NamedSharding(mesh, P(baxes)) for k in batch_struct}
    return jax.jit(
        step,
        in_shardings=(shard_p, shard_p, shard_b, NamedSharding(mesh, P())),
        out_shardings=(NamedSharding(mesh, P()), shard_p, shard_p),
        donate_argnums=(0, 1) if donate else (),
    )


class Trainer:
    """End-to-end training driver: schedule-aware step compilation,
    checkpointing, metrics.

    ``ckpt_every=N`` saves a fault-tolerance checkpoint every N steps
    into ``ckpt_dir`` (a ``CheckpointManager`` full+sparse-delta chain);
    the checkpoint carries EVERY worker's params / residual / velocity /
    pending-ring copy (``checkpoint.elastic.stack_worker_copies``), so
    ``restore_checkpoint`` resumes bitwise mid-run at the same worker
    count and elastically (remainder-rule redistribution) at a different
    one. ``ckpt_full_every`` sets the chain's full-snapshot cadence.
    """

    def __init__(self, arch_cfg: ModelConfig, tc: TrainConfig,
                 mesh: Optional[Mesh] = None,
                 pc: Optional[ParallelConfig] = None,
                 ckpt_dir: Optional[str] = None,
                 ckpt_every: int = 0,
                 ckpt_full_every: int = 4):
        self.model = get_model(arch_cfg)
        self.cfg = arch_cfg
        self.tc = tc
        self.mesh = mesh
        self.pc = pc or ParallelConfig()
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        self.ckpt_full_every = ckpt_full_every
        self.schedule = DensitySchedule(
            target=tc.density,
            warmup_steps_per_stage=tc.warmup_steps_per_stage,
            dense_warmup=tc.dense_warmup)
        self._sync = make_gradient_sync(tc, mesh)
        self._steps: dict[float, Callable] = {}
        self._ckpt_mgr = None

    def init_state(self, seed: Optional[int] = None) -> TrainState:
        params = self.model.init_params(
            self.tc.seed if seed is None else seed)
        return TrainState(params=params, rgc=self._sync.init(params), step=0)

    def density_at(self, step: int) -> float:
        """Density for this step: a ``warmup`` correction in the optimizer
        spec owns the schedule when present; otherwise the TrainConfig's
        warm-up fields drive the trainer-level DensitySchedule."""
        d = self._sync.scheduled_density(step)
        return self.schedule.density_at(step) if d is None else d

    def _step_fn(self, density: float) -> Callable:
        # "dense" compiles the same step at every density (make_train_step
        # pins dens=1.0): key the cache on the EFFECTIVE density so a
        # warm-up schedule doesn't trigger redundant recompiles
        if self.tc.optimizer == "dense":
            density = 1.0
        if density not in self._steps:
            self._steps[density] = make_train_step(
                self.model, self.mesh, self.pc, self.tc, density=density,
                donate=False)
        return self._steps[density]

    # -- fault-tolerance checkpointing --------------------------------------

    def _num_workers(self) -> int:
        return int(self.mesh.devices.size) if self.mesh is not None else 1

    def _manager(self):
        from repro.checkpoint import CheckpointManager
        if self.ckpt_dir is None:
            raise ValueError("Trainer has no ckpt_dir")
        if self._ckpt_mgr is None:
            self._ckpt_mgr = CheckpointManager(
                self.ckpt_dir, full_every=self.ckpt_full_every)
        return self._ckpt_mgr

    def save_checkpoint(self, state: TrainState) -> str:
        """Persist the FULL training state — params, optimizer/sync state
        (residual, velocity, thresholds) and any in-flight pending
        message ring — with one row per worker, so restore is bitwise."""
        from repro.checkpoint import stack_worker_copies
        stacked = stack_worker_copies({"params": state.params,
                                       "rgc": state.rgc})
        return self._manager().save(
            state.step, stacked,
            metadata={"num_workers": self._num_workers(),
                      "schedule": self.tc.schedule,
                      "optimizer": self.tc.optimizer})

    def restore_checkpoint(self, step: Optional[int] = None) -> TrainState:
        """Resume from ``ckpt_dir`` at ``step`` (default latest).

        Same worker count: every worker's buffers go back to its device
        — bitwise continuation (the crash/resume battery's contract).
        Different worker count: pending rings are reabsorbed into each
        old worker's residual, then residual/velocity redistribute by
        the remainder SUM rule and scalar state by the copy rule
        (``checkpoint.elastic``); the pending ring restarts zeroed.
        """
        import numpy as np

        from repro.checkpoint import (redistribute_leaf_states,
                                      redistribute_tree, unstack_to_devices)
        from repro.core.overlap import ScheduleState, reabsorb_pending

        mgr = self._manager()
        if step is None:
            step = mgr.latest_step()
            if step is None:
                raise FileNotFoundError(f"no checkpoints in {self.ckpt_dir}")
        p_ckpt = int(mgr.metadata.get("num_workers", 1))
        p_now = self._num_workers()

        template_state = self.init_state()
        tree = {"params": template_state.params, "rgc": template_state.rgc}
        template = jax.tree.map(
            lambda l: np.zeros((p_ckpt,) + tuple(np.shape(l)),
                               np.asarray(l).dtype), tree)
        stacked = mgr.restore(template, step=step)

        if p_now != p_ckpt:
            # reabsorb each OLD worker's in-flight ring into its residual
            # (packed for a 1/p_ckpt-weighted apply — not replayable at
            # p_now), then redistribute
            rgc_workers = []
            for i in range(p_ckpt):
                st_i = jax.tree.map(lambda s: np.asarray(s)[i],
                                    stacked["rgc"])
                params_i = jax.tree.map(lambda s: np.asarray(s)[i],
                                        stacked["params"])
                rgc_workers.append(
                    reabsorb_pending(self._sync, params_i, st_i))
            stacked_leaf = jax.tree.map(
                lambda *xs: np.stack([np.asarray(x) for x in xs]),
                *rgc_workers)
            new_leaf = redistribute_leaf_states(stacked_leaf, p_now)
            new_params = redistribute_tree(
                stacked["params"], p_now, lambda kp, l: False)
            dev = unstack_to_devices({"params": new_params,
                                      "leaf": new_leaf}, self.mesh)
            fresh = self._sync.init(dev["params"])
            rgc = (ScheduleState(leaf=dev["leaf"], pending=fresh.pending)
                   if isinstance(fresh, ScheduleState) else dev["leaf"])
            return TrainState(params=dev["params"], rgc=rgc, step=step)

        dev = unstack_to_devices(stacked, self.mesh)
        return TrainState(params=dev["params"], rgc=dev["rgc"], step=step)

    def run(self, state: TrainState, batches, num_steps: int,
            log_every: int = 10, log_fn=print,
            on_metrics: Optional[Callable[[int, float, float], None]] = None
            ) -> TrainState:
        """``on_metrics(step, density, loss)`` fires every step (forces a
        per-step device sync — metrics/convergence harness use).

        Each step is a ``train_step`` profiler step whose host spans are
        ``trainer.feed`` (next batch onto the device), ``trainer.dispatch``
        (the step call), ``trainer.metrics`` (``on_metrics`` and logging)
        and ``trainer.checkpoint``; outside a profile they cost one check
        each."""
        it = iter(batches)
        for _ in range(num_steps):
            with StepTraceAnnotation("train_step", step_num=state.step):
                with TraceAnnotation("trainer.feed"):
                    batch = {k: jnp.asarray(v) for k, v in next(it).items()}
                density = self.density_at(state.step)
                fn = self._step_fn(density)
                with TraceAnnotation("trainer.dispatch"):
                    loss, params, rgc_state = fn(
                        state.params, state.rgc, batch,
                        jnp.float32(self.tc.lr))
                state = TrainState(params, rgc_state, state.step + 1)
                with TraceAnnotation("trainer.metrics"):
                    if on_metrics is not None:
                        on_metrics(state.step, density, float(loss))
                    if log_every and state.step % log_every == 0:
                        log_fn(f"step {state.step:5d}  density "
                               f"{density:.4%}  loss {float(loss):.4f}")
                if (self.ckpt_dir and self.ckpt_every
                        and state.step % self.ckpt_every == 0):
                    with TraceAnnotation("trainer.checkpoint"):
                        self.save_checkpoint(state)
        if self.ckpt_dir and self._manager().latest_step() != state.step:
            with TraceAnnotation("trainer.checkpoint"):
                self.save_checkpoint(state)
        return state
