"""Multi-device flat-arena parity program, run as a subprocess by
tests/test_arena.py with 8 forced host devices (the XLA flag must be set
before jax init, so it cannot run inside the main pytest process).

Checks that ``fuse_leaves=True`` (flat residual arenas: one fused
accumulate-gather + segmented select + mask + pack per arena) produces
BITWISE identical synced params and residual state to the per-leaf
pipeline when every worker compresses a different local gradient:

 1. mixed-size tree (both §5.5 sparse classes + dense fallback leaves,
    non-block-multiple sizes) on the ("data",)=8 mesh, multi-step;
 2. the same with DGC corrections ("momentum+clip(threshold_bsearch)");
 3. a single-leaf model (one slot per arena — nothing to coalesce);
 4. fused arenas feeding the bucketed transport (arena messages ride
    straight into bucket assignment).
"""
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

TREE_SIZES = {"big": (1 << 20) + 17, "mid": 96 * 1024 + 3,
              "mid2": 33_001, "small": 1_000}
SINGLE_SIZES = {"w": (1 << 20) + 17}


def run_steps(fuse, sizes, optimizer="rgc", **kw):
    mesh = _make_mesh((8,), ("data",))
    rng = np.random.default_rng(0)
    params = {k: jnp.asarray(rng.standard_normal(n), jnp.float32)
              for k, n in sizes.items()}
    grads = {k: jnp.asarray(rng.standard_normal((8, STEPS, n)) * 0.01,
                            jnp.float32)
             for k, n in sizes.items()}

    sync = build_gradient_sync(
        optimizer, sync_axes=("data",), density=0.01, momentum=0.9,
        fuse_leaves=fuse, **kw)
    state0 = sync.init(params)

    def worker(gs, p, st):
        for t in range(STEPS):
            g_t = {k: g[0, t] for k, g in gs.items()}
            p, st = sync.update(g_t, st, p, jnp.float32(LR))
        return p, st

    f = jax.jit(jax.shard_map(
        worker, mesh=mesh,
        in_specs=({k: P(("data",)) for k in sizes}, P(),
                  jax.tree.map(lambda _: P(), state0)),
        out_specs=(P(), jax.tree.map(lambda _: P(), state0)),
        check_vma=False))
    p2, st2 = f(grads, params, state0)
    return (jax.tree.map(np.asarray, p2), jax.tree.map(np.asarray, st2))


def check_bitwise(name, got, want):
    leaves_g = jax.tree.leaves(got)
    leaves_w = jax.tree.leaves(want)
    same = all(a.dtype == b.dtype and np.array_equal(a, b, equal_nan=True)
               for a, b in zip(leaves_g, leaves_w))
    if not same:
        for a, b in zip(leaves_g, leaves_w):
            if not np.array_equal(a, b, equal_nan=True):
                print(f"  mismatch: max|d|="
                      f"{np.max(np.abs(a.astype(np.float64) - b)):.3e}")
    check(name, same)


def test_mixed_tree():
    ref_p, ref_s = run_steps(False, TREE_SIZES)
    got_p, got_s = run_steps(True, TREE_SIZES)
    check_bitwise("arena == per-leaf params (mixed tree, 8 workers)",
                  got_p, ref_p)
    check_bitwise("arena == per-leaf state (mixed tree, 8 workers)",
                  got_s, ref_s)


def test_corrections():
    spec = "momentum+clip(threshold_bsearch)"
    ref = run_steps(False, TREE_SIZES, optimizer=spec, local_clip=1.0)
    got = run_steps(True, TREE_SIZES, optimizer=spec, local_clip=1.0)
    check_bitwise("arena == per-leaf (DGC corrections, 8 workers)",
                  got, ref)


def test_single_leaf():
    ref = run_steps(False, SINGLE_SIZES)
    got = run_steps(True, SINGLE_SIZES)
    check_bitwise("arena == per-leaf (single-leaf model)", got, ref)


def test_bucketed_transport():
    kw = dict(transport="bucketed_allgather", bucket_bytes=40_000)
    ref = run_steps(False, TREE_SIZES, **kw)
    got = run_steps(True, TREE_SIZES, **kw)
    check_bitwise("arena == per-leaf (bucketed transport)", got, ref)


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    fns = {"mixed": test_mixed_tree,
           "corrections": test_corrections,
           "single": test_single_leaf,
           "bucketed": test_bucketed_transport}
    if which == "all":
        for fn in fns.values():
            fn()
    else:
        fns[which]()
    print("OK")
