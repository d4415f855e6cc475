"""Faults planted under the timed path, to show that the check catches
each fault a training cell can have. Used by the tests only.

* ``unchanged``: the step returns its state unchanged;
* ``half_batch``: the step sees the first half of its batch, so the mean
  is taken over the rest;
* ``no_exchange``: the sparse all-gather is left out, so each worker
  applies only its own message.
"""
from __future__ import annotations

import contextlib

FAULTS = ("unchanged", "half_batch", "no_exchange")


def _wrap_step(trainer, broken):
    orig = trainer._step_fn

    def step_fn(density):
        return broken(orig(density))

    trainer._step_fn = step_fn


@contextlib.contextmanager
def plant(name: str, trainer):
    if name == "unchanged":
        def broken(fn):
            def step(params, rgc, batch, lr):
                loss, _, _ = fn(params, rgc, batch, lr)
                return loss, params, rgc
            return step
        _wrap_step(trainer, broken)
        yield
    elif name == "half_batch":
        def broken(fn):
            def step(params, rgc, batch, lr):
                half = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
                return fn(params, rgc, half, lr)
            return step
        _wrap_step(trainer, broken)
        yield
    elif name == "no_exchange":
        from repro.core.transport import FusedAllgather
        orig = FusedAllgather.allgather
        FusedAllgather.allgather = lambda self, msgs: [m[None] for m in msgs]
        try:
            yield
        finally:
            FusedAllgather.allgather = orig
    else:
        raise ValueError(f"unknown fault {name!r}; expected one of {FAULTS}")
