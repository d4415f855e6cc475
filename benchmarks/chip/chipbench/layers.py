"""The layers' calls on their own, for the traced run.

The program has no named scopes yet, so the step's trace cannot be split
by layer. Each layer is instead called alone, on the trainer's own state
after the window, as a jitted program whose name starts with
``jit_bench_`` (``tracing.reduce`` sums its device time per program):

* ``bench_fwd_bwd``: ``value_and_grad`` of the model's loss on one chip's
  batch;
* ``bench_select``: the arena path's fused selection
  (``GradientSync._select_groups``) on one chip, on accumulated arenas
  made by ``bench_accumulate``, at each of the ``interval`` phases in
  turn (one fresh search, then reuses).

The sync pipeline's time is read from the step's own trace instead (the
step's device time less ``bench_fwd_bwd``'s; ``metrics/sync_ms.py``), so
that the traced run compiles one selection program and not two.

These calls reach into the program's internals: ``GradientSync._context``,
``_plan``, ``_accumulate_group`` and ``_select_groups`` (and the
harness's ``Trainer._step_fn`` and ``_sync``). A change to the program
has to keep these names and their signatures, or the traced run fails
and the metrics that read ``jit_bench_select`` go silent.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


class LayerCalls:
    """The jitted layer programs, compiled on the trainer's state."""

    def __init__(self, trainer, state, batch: dict, per_chip: int,
                 density: float):
        from repro.train.trainer import make_gradient_sync

        from . import check
        model, tc = trainer.model, trainer.tc
        self.params, self.rgc = state.params, state.rgc
        del state
        p1 = check.copies(self.params)[0]
        self.batch1 = jax.device_put(
            {k: v[:per_chip] for k, v in batch.items()},
            next(iter(jax.tree.leaves(p1)[0].devices())))

        def bench_fwd_bwd(params, batch):
            return jax.value_and_grad(model.loss)(params, batch)

        self.fwd_bwd = jax.jit(bench_fwd_bwd)
        _, self.grads1 = self.fwd_bwd(p1, self.batch1)
        del p1

        one = make_gradient_sync(tc, None)
        plan_box = {}

        def bench_accumulate(g, s, p):
            treedef, raw, lg, lp, ls, _ = one._context(g, s, p)
            plan = one._plan(g, treedef, raw, density, False)
            plan_box["plan"] = plan
            return [one._accumulate_group(grp, comp, lg, lp, ls)
                    for grp, comp in zip(plan.groups, plan.group_comps)]

        def bench_select(accs):
            plan = plan_box["plan"]
            return one._select_groups(plan.groups, plan.group_comps, accs)

        self.accumulate = jax.jit(bench_accumulate)
        self.select = jax.jit(bench_select)
        accs = self._phase_accs(0)
        plan = plan_box["plan"]
        self.interval = max((getattr(c, "interval", 1)
                             for c in plan.group_comps), default=1)
        self.select_bytes = sum(
            a[0].size * 4 for a in accs) + 8 * sum(
                slot.k for grp in plan.groups for slot in grp.slots)
        del accs

    def _phase_accs(self, i: int):
        """Accumulated arenas on chip 0 with every leaf at phase ``i`` of
        the threshold's refresh interval."""
        from . import check
        phase = jax.tree.map(
            lambda s: s._replace(interval=jnp.int32(i)),
            check.copies(self.rgc)[0],
            is_leaf=lambda x: hasattr(x, "interval"))
        return self.accumulate(self.grads1, phase,
                               check.copies(self.params)[0])

    def warm(self) -> None:
        """Compile (or load) each program with one call."""
        from . import check
        jax.block_until_ready(self.fwd_bwd(check.copies(self.params)[0],
                                           self.batch1))
        jax.block_until_ready(self.select(self._phase_accs(0)))

    def run(self, rounds: int) -> dict:
        """``rounds`` calls of fwd/bwd and ``rounds`` passes over the
        selection's phases; returns the call counts."""
        from . import check
        p1 = check.copies(self.params)[0]
        for _ in range(rounds):
            out = self.fwd_bwd(p1, self.batch1)
            jax.block_until_ready(out)
            del out
        del p1
        for _ in range(rounds):
            for i in range(self.interval):
                out = self.select(self._phase_accs(i))
                jax.block_until_ready(out)
                del out
        return {"fwd_bwd": rounds, "select": rounds * self.interval}
