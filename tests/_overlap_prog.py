"""Multi-device overlap-schedule differential program, run as a subprocess
by tests/test_overlap.py with 8 forced host devices (the XLA flag must be
set before jax init, so it cannot run inside the main pytest process).

The §5.6 ``chunked`` schedule's contract: pipelined per-chunk dispatch
changes ONLY the number/order of transport collectives — params and
optimizer state stay BITWISE identical (equal sha256 digests) to the
``sequential`` full-tree-barrier schedule, for every registered sparse
transport, with the flat arenas on AND off, under jit, when every worker
compresses a different local gradient:

  * ``fused``        — fused_allgather on the ("data",)=8 mesh;
  * ``bucketed``     — bucketed_allgather (chunks feeding bucket
                       assignment) on the ("data",)=8 mesh;
  * ``per_leaf``     — per_leaf_allgather on the ("data",)=8 mesh;
  * ``hierarchical`` — the two-level transport on the ("node","local")
                       2x4 mesh (inter-node sparse hop + intra psum);
  * ``corrections``  — fused transport + the full DGC pipeline
                       ("momentum+clip(threshold_bsearch)");
  * ``stale1``       — the one-step-delayed schedule vs an explicitly
                       delayed sequential reference: running sequential
                       on the SAME grads and applying each step's
                       gathered messages one step late must reproduce
                       stale1's params bitwise (8 workers);
  * ``stalek``       — same differential for the K-deep ring
                       (``staleK(k=2)``): the reference holds each
                       step's packed messages in a 2-deep deque and
                       applies them two steps late.

Chunk budget is set small relative to the tree so every case really
splits into >= 2 chunks (asserted via a WallClockTimer collective count
in the in-process tests; here the byte budget math is deterministic).
"""
import hashlib
import sys

from harness.cluster import check, force_host_devices

force_host_devices(8)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import build_gradient_sync
from repro.launch.mesh import _make_mesh

STEPS = 3
LR = 0.1

# mixed §5.5 classes, non-block-multiple sizes; small enough to keep the
# 8-device jit compiles fast, large enough to split into several chunks
TREE_SIZES = {"big": (1 << 18) + 17, "mid": 96 * 1024 + 3,
              "mid2": 33_001, "small": 1_000}
CHUNK_BYTES = 260_000      # several chunks over TREE_SIZES' f32 bytes


def digest(tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def make_mesh(transport):
    if transport == "hierarchical":
        return _make_mesh((2, 4), ("node", "local")), ("node", "local")
    return _make_mesh((8,), ("data",)), ("data",)


def make_tree(seed=0):
    rng = np.random.default_rng(seed)
    params = {k: jnp.asarray(rng.standard_normal(n), jnp.float32)
              for k, n in TREE_SIZES.items()}
    grads = {k: jnp.asarray(rng.standard_normal((8, STEPS, n)) * 0.01,
                            jnp.float32)
             for k, n in TREE_SIZES.items()}
    return params, grads


def run_steps(schedule, transport, fuse, optimizer="rgc", **kw):
    mesh, axes = make_mesh(transport)
    params, grads = make_tree()

    sync = build_gradient_sync(
        optimizer, transport=transport, sync_axes=axes, density=0.01,
        momentum=0.9, fuse_leaves=fuse, schedule=schedule,
        bucket_bytes=CHUNK_BYTES, **kw)
    state0 = sync.init(params)

    def worker(gs, p, st):
        for t in range(STEPS):
            g_t = {k: g[0, t] for k, g in gs.items()}
            p, st = sync.update(g_t, st, p, jnp.float32(LR))
        return p, st

    f = jax.jit(jax.shard_map(
        worker, mesh=mesh,
        in_specs=({k: P(axes) for k in TREE_SIZES}, P(),
                  jax.tree.map(lambda _: P(), state0)),
        out_specs=(P(), jax.tree.map(lambda _: P(), state0)),
        check_vma=False))
    p2, st2 = f(grads, params, state0)
    return (jax.tree.map(np.asarray, p2), jax.tree.map(np.asarray, st2))


def check_bitwise(name, got, want):
    leaves_g = jax.tree.leaves(got)
    leaves_w = jax.tree.leaves(want)
    same = (len(leaves_g) == len(leaves_w)
            and all(a.dtype == b.dtype
                    and np.array_equal(a, b, equal_nan=True)
                    for a, b in zip(leaves_g, leaves_w)))
    if not same:
        for a, b in zip(leaves_g, leaves_w):
            if not np.array_equal(a, b, equal_nan=True):
                print(f"  mismatch: max|d|="
                      f"{np.max(np.abs(a.astype(np.float64) - b)):.3e}")
    check(name, same)


def diff_case(transport, optimizer="rgc", **kw):
    """chunked == sequential: params + state + digests, fuse on and off."""
    for fuse in (False, True):
        ref_p, ref_s = run_steps("sequential", transport, fuse,
                                 optimizer=optimizer, **kw)
        got_p, got_s = run_steps("chunked", transport, fuse,
                                 optimizer=optimizer, **kw)
        tag = f"{transport} fuse={fuse}"
        check_bitwise(f"chunked == sequential params ({tag})", got_p, ref_p)
        check_bitwise(f"chunked == sequential state ({tag})", got_s, ref_s)
        check(f"chunked == sequential digest ({tag})",
              digest((got_p, got_s)) == digest((ref_p, ref_s)))


def test_fused():
    diff_case("fused_allgather")


def test_bucketed():
    diff_case("bucketed_allgather")


def test_per_leaf():
    diff_case("per_leaf_allgather")


def test_hierarchical():
    diff_case("hierarchical")


def test_corrections():
    diff_case("fused_allgather",
              optimizer="momentum+clip(threshold_bsearch)", local_clip=1.0)


def delayed_case(schedule, delay):
    """``schedule`` == sequential-with-explicitly-delayed-apply, 8 workers.

    The reference re-runs the SEQUENTIAL pipeline but holds each step's
    packed messages in a ``delay``-deep FIFO: at step t it applies the
    messages packed at t-delay (zero-count for the first ``delay``
    steps). That is exactly the double-buffer/ring semantics
    ``Stale1Schedule`` / ``StaleKSchedule`` implement inside one update,
    so params AND residual state must match bitwise.
    """
    mesh, axes = make_mesh("fused_allgather")
    params, grads = make_tree()

    got_p, got_s = run_steps(schedule, "fused_allgather", True)

    # reference: a sequential sync whose transport dispatch is delayed
    # by hand — compress with the REAL pipeline, but gather/apply the
    # buffer packed ``delay`` steps ago
    sync = build_gradient_sync(
        "rgc", transport="fused_allgather", sync_axes=axes, density=0.01,
        momentum=0.9, fuse_leaves=True, schedule="sequential",
        bucket_bytes=CHUNK_BYTES)
    state0 = sync.init(params)
    pending0 = sync._pending_zeros(params)

    def worker(gs, p, st):
        ring = [list(pending0) for _ in range(delay)]
        for t in range(STEPS):
            g_t = {k: g[0, t] for k, g in gs.items()}
            (treedef, leaves_raw, leaves_g, leaves_p, leaves_s,
             n_workers) = sync._context(g_t, st, p)
            plan = sync._plan(g_t, treedef, leaves_raw, sync.density,
                              False)
            new_states = list(leaves_s)
            new_params = list(leaves_p)
            messages, meta = sync._compress_plan(
                plan, leaves_g, leaves_p, leaves_s, new_states)
            gathered = sync._gather(ring[0])           # delay steps late
            sync._apply_gathered(gathered, meta, leaves_p, new_params,
                                 jnp.float32(LR), n_workers)
            for i in plan.dense:
                g_mean = sync._dense_reduce(i, leaves_g)
                sync._dense_apply(i, g_mean, leaves_p, leaves_s,
                                  new_states, new_params, jnp.float32(LR))
            ring = ring[1:] + [messages]
            p = jax.tree.unflatten(treedef, new_params)
            st = jax.tree.unflatten(treedef, new_states)
        return p, st

    f = jax.jit(jax.shard_map(
        worker, mesh=mesh,
        in_specs=({k: P(axes) for k in TREE_SIZES}, P(),
                  jax.tree.map(lambda _: P(), state0)),
        out_specs=(P(), jax.tree.map(lambda _: P(), state0)),
        check_vma=False))
    ref_p, ref_s = f(grads, params, state0)
    ref_p = jax.tree.map(np.asarray, ref_p)
    ref_s = jax.tree.map(np.asarray, ref_s)

    check_bitwise(f"{schedule} params == {delay}-delayed-sequential "
                  f"reference (8 dev)", got_p, ref_p)
    check_bitwise(f"{schedule} leaf state == {delay}-delayed-sequential "
                  f"reference", got_s.leaf, ref_s)


def test_stale1():
    delayed_case("stale1", 1)


def test_stalek():
    # STEPS=3 with k=2: step 2 applies step 0's packed ring slot, and
    # two real generations are left in flight at the end
    delayed_case("staleK(k=2)", 2)


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    fns = {"fused": test_fused,
           "bucketed": test_bucketed,
           "per_leaf": test_per_leaf,
           "hierarchical": test_hierarchical,
           "corrections": test_corrections,
           "stale1": test_stale1,
           "stalek": test_stalek}
    if which == "all":
        for fn in fns.values():
            fn()
    else:
        fns[which]()
    print("OK")
