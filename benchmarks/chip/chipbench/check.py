"""The comparison that decides a run's ``correct``.

The set-up drives the trainer's own step from the seed through its first
three steps, on the window's feed. Three numbers compare what it
produced with the plain reference (``chipref``) run from the same
weights and batches, at ``highest`` precision, after the window:

* ``loss_gap``: the worst of the three steps' losses, ``|L - L_ref| /
  |L_ref|``; ``loss1_gap`` the same of the first step alone (the forward
  pass at the initial weights, before any update);
* ``grad_gap``: the first gradient as the optimizer got it, worked out
  from the state after one step (the mean over workers of the residual,
  plus the parameters' change over ``lr``), as the worst leaf's
  ``|‖g‖ - ‖g_ref‖| / max(‖g_ref‖, median leaf ‖g_ref‖)``;
* ``change_gap``: the parameters' change after three steps, the same way
  by the worst leaf. Leaves whose reference gradient is under a
  thousandth of the median leaf's are left out: they move by round-off.

On several chips each chip's copy of the parameters is read, and the
worst copy counts. A reference "put in the program's place" (a control
in lower precision, or a planted fault) is read the same way.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

NUMBERS = ("loss_gap", "loss1_gap", "grad_gap", "change_gap")
NEGLIGIBLE = 1e-3       # of the median leaf's reference gradient


@dataclass
class Readings:
    losses: list = field(default_factory=list)    # steps 1..3
    grad: list = field(default_factory=list)      # per copy: leaf norms
    change: list = field(default_factory=list)    # per copy: leaf norms


@jax.jit
def grad_norms(p0, p1, v_mean, lr):
    """Per leaf ‖v + (p0 - p1) / lr‖ in float32."""
    return jnp.stack([
        jnp.linalg.norm((v.astype(jnp.float32) + (a.astype(jnp.float32)
                         - b.astype(jnp.float32)) / lr).reshape(-1))
        for a, b, v in zip(jax.tree.leaves(p0), jax.tree.leaves(p1),
                           jax.tree.leaves(v_mean))])


@jax.jit
def change_norms(p0, p3):
    return jnp.stack([
        jnp.linalg.norm((b.astype(jnp.float32) - a.astype(jnp.float32))
                        .reshape(-1))
        for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p3))])


@jax.jit
def tree_mean(trees):
    return jax.tree.map(lambda *xs: sum(x.astype(jnp.float32) for x in xs)
                        / len(xs), *trees)


def copies(tree) -> list:
    """One tree per device holding ``tree`` (a replicated array keeps one
    copy on each of its devices), in device-id order."""
    leaves, treedef = jax.tree.flatten(tree)
    per_dev = [sorted(leaf.addressable_shards, key=lambda s: s.device.id)
               for leaf in leaves]
    return [jax.tree.unflatten(treedef, [shards[i].data for shards in per_dev])
            for i in range(len(per_dev[0]))]


def to_device(tree, device):
    return jax.device_put(tree, device)


def gaps(prog: Readings, ref: Readings) -> dict:
    """The three compared numbers of ``prog`` against ``ref``."""
    lp, lr_ = np.asarray(prog.losses, np.float64), np.asarray(ref.losses,
                                                               np.float64)
    rel = np.abs(lp - lr_) / np.abs(lr_)
    if not np.all(np.isfinite(lp)):
        rel[:] = math.inf
    loss_gap, loss1_gap = float(np.max(rel)), float(rel[0])
    g_ref = np.asarray(ref.grad[0], np.float64)
    med = float(np.median(g_ref))
    grad_gap = max(float(np.max(np.abs(np.asarray(g, np.float64) - g_ref)
                                / np.maximum(g_ref, med)))
                   for g in prog.grad)
    keep = g_ref >= NEGLIGIBLE * med
    d_ref = np.asarray(ref.change[0], np.float64)[keep]
    med_d = float(np.median(d_ref))
    change_gap = max(float(np.max(
        np.abs(np.asarray(d, np.float64)[keep] - d_ref)
        / np.maximum(d_ref, med_d))) for d in prog.change)
    out = {"loss_gap": loss_gap, "loss1_gap": loss1_gap,
           "grad_gap": grad_gap, "change_gap": change_gap}
    return {k: (math.inf if not math.isfinite(v) else v)
            for k, v in out.items()}


def compared(limits: dict) -> list[str]:
    """The numbers a cell's limits compare (a limit of None leaves one
    out)."""
    return [k for k in NUMBERS if limits.get(k) is not None]


def verdict(gaps: dict, limits: dict) -> bool:
    return all(gaps[k] <= limits[k] for k in compared(limits))


# --- the program's side ----------------------------------------------------

def _device(tree):
    return next(iter(jax.tree.leaves(tree)[0].devices()))


def program_grad(p0, state1, lr: float) -> list:
    """Leaf norms of the first gradient per device copy, from the trainer
    state after one step (one residual per worker, one worker per
    device)."""
    residual = jax.tree.map(lambda s: s.residual, state1.rgc,
                            is_leaf=lambda x: hasattr(x, "residual"))
    p0c = copies(p0)[0]
    dev0 = _device(p0c)
    v_mean = tree_mean([to_device(v, dev0) for v in copies(residual)])
    return [np.asarray(grad_norms(p0c, to_device(p1, dev0), v_mean,
                                  jnp.float32(lr)))
            for p1 in copies(state1.params)]


def program_change(p0, p3) -> list:
    p0c = copies(p0)[0]
    dev0 = _device(p0c)
    return [np.asarray(change_norms(p0c, to_device(p, dev0)))
            for p in copies(p3)]


# --- the reference's side --------------------------------------------------

def loss_and_grad(fam, cfg: dict, nx, rows_per_call: int):
    """``(params, tokens) -> (mean loss, mean-loss gradient)``, summed over
    blocks of ``rows_per_call`` rows so that the reference fits."""
    def nll(p, t):
        return fam.nll_sum(cfg, p, t, nx)

    vg = jax.jit(jax.value_and_grad(nll, has_aux=True))

    def fn(params, tokens):
        params = jax.tree.map(nx.cast, params)
        tot = cnt = 0.0
        grads = None
        for r in range(0, tokens.shape[0], rows_per_call):
            (s, c), g = vg(params, jnp.asarray(tokens[r:r + rows_per_call]))
            tot, cnt = tot + s, cnt + c
            grads = g if grads is None else _add(grads, g)
        return tot / cnt, jax.tree.map(lambda x: x / cnt, grads)

    return fn


@jax.jit
def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


def reference_run(fam, rgc, cfg: dict, params0, batches: list, n_workers: int,
                  st, nx, rows_per_call: int, fault: str | None = None,
                  params_dtype=None) -> Readings:
    """The reference's three steps from ``params0`` on ``batches`` (global
    batches; worker ``w`` takes the ``w``-th slice of rows).

    ``fault`` plants one in the reference: ``half`` (each worker's loss
    over the first half of its rows only), ``no_exchange`` (each worker
    applies only its own message).
    """
    lg = loss_and_grad(fam, cfg, nx, rows_per_call)
    dtype = params_dtype
    p0 = params0 if dtype is None else jax.tree.map(
        lambda p: p.astype(dtype), params0)
    params = [p0] * n_workers
    states = [rgc.init_worker(p0, nx) for _ in range(n_workers)]
    out = Readings()
    for step, batch in enumerate(batches[:3]):
        toks = np.asarray(batch["tokens"])
        rows = toks.shape[0] // n_workers
        loss, msg_sum, new_params = 0.0, None, []
        for w in range(n_workers):
            mine = toks[w * rows:(w + 1) * rows]
            if fault == "half":
                mine = mine[:rows // 2]
            lw, g = lg(params[w], mine)
            loss += float(lw) / n_workers
            msg, states[w] = rgc.worker_update(
                g, states[w], st=st, n_workers=n_workers, dtype=nx.dtype)
            if fault == "no_exchange":
                new_params.append(rgc.apply(params[w], msg, st.lr,
                                            n_workers))
            else:
                msg_sum = msg if msg_sum is None else rgc.tree_add(msg_sum,
                                                                   msg)
        if fault != "no_exchange":
            new_params = [rgc.apply(params[0], msg_sum, st.lr, n_workers)
                          ] * n_workers
        params = new_params
        out.losses.append(loss)
        if step == 0:
            v_mean = tree_mean([s["v"] for s in states])
            out.grad = [np.asarray(grad_norms(p0, p, v_mean,
                                              jnp.float32(st.lr)))
                        for p in _distinct(params)]
    out.change = [np.asarray(change_norms(p0, p)) for p in _distinct(params)]
    return out


def _distinct(trees: list) -> list:
    seen, out = set(), []
    for t in trees:
        if id(t) not in seen:
            seen.add(id(t))
            out.append(t)
    return out
