"""Multi-device transport parity program, run as a subprocess by
test_transport.py with 8 forced host devices (the XLA flag must be set
before jax init, so it cannot run inside the main pytest process).

Checks that ``bucketed_allgather`` and ``hierarchical`` produce BITWISE
identical synced params and residual state to ``fused_allgather`` when
every worker compresses a different local gradient:

 1. bucketed vs fused on the harness ("data",)=8 mesh, over a mixed-size
    pytree whose messages do NOT fill buckets evenly (non-bucket-multiple)
    and with a bucket budget small enough to force several buckets.
 2. hierarchical vs fused on a 2-axis ("node","local") = (2,4) mesh — the
    §5.4 intra-node dense psum + inter-node sparse allgather composition.
 3. both, on a single-leaf model (one big sparse leaf, nothing to fuse).
 4. row-order sanity: the hierarchical two-hop exchange reassembles the
    gathered message matrix in the same worker order as the flat joint
    all_gather (checked implicitly by 2/3 being bitwise, and explicitly
    on a tagged payload here).
"""
import sys

from harness.cluster import check, force_host_devices

force_host_devices(8)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import build_gradient_sync
from repro.core import sync as sync_lib
from repro.launch.mesh import _make_mesh

STEPS = 3
LR = 0.1

# Mixed-size tree: >=4 MiB -> threshold_bsearch, 128 KB..4 MiB -> trimmed
# top-k, < 128 KB -> dense psum fallback. Sizes are deliberately not round
# so messages never tile a bucket budget exactly.
TREE_SIZES = {"big": (1 << 20) + 17, "mid": 96 * 1024 + 3,
              "mid2": 33_001, "small": 1_000}
SINGLE_SIZES = {"w": (1 << 20) + 17}


def make_mesh(axes):
    shapes = {("data",): (8,), ("node", "local"): (2, 4)}
    return _make_mesh(shapes[axes], axes)


def run_steps(transport, axes, sizes, **transport_kw):
    """STEPS sync steps on the mesh; every worker sees its own gradient
    stream. Returns (params, state) trees as host arrays."""
    mesh = make_mesh(axes)
    rng = np.random.default_rng(0)
    params = {k: jnp.asarray(rng.standard_normal(n), jnp.float32)
              for k, n in sizes.items()}
    # [workers, STEPS, n] per leaf, sharded over the batch axes on dim 0
    grads = {k: jnp.asarray(rng.standard_normal((8, STEPS, n)) * 0.01,
                            jnp.float32)
             for k, n in sizes.items()}

    sync = build_gradient_sync(
        "rgc", transport=transport, sync_axes=axes, density=0.01,
        momentum=0.9, **transport_kw)
    state0 = sync.init(params)

    def worker(gs, p, st):
        for t in range(STEPS):
            g_t = {k: g[0, t] for k, g in gs.items()}
            p, st = sync.update(g_t, st, p, jnp.float32(LR))
        return p, st

    f = jax.jit(jax.shard_map(
        worker, mesh=mesh,
        in_specs=({k: P(axes) for k in sizes}, P(),
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


def test_row_order():
    """Hierarchical gather must order rows exactly as the joint gather."""
    mesh = make_mesh(("node", "local"))

    def worker(x):
        flat = sync_lib.sparse_allgather(x[0], ("node", "local"))
        hier = sync_lib.hierarchical_allgather(x[0], ("node",), "local")
        return (flat == hier).all(), flat[:, 0]

    f = jax.jit(jax.shard_map(
        worker, mesh=mesh, in_specs=(P(("node", "local")),),
        out_specs=(P(), P()), check_vma=False))
    # tag each worker's message with its global rank
    tags = jnp.arange(8, dtype=jnp.float32).reshape(8, 1) * jnp.ones((8, 4))
    same, order = f(tags)
    check("hierarchical row order == joint all_gather order", bool(same))
    check("rows are node-major rank order",
          np.array_equal(np.asarray(order), np.arange(8, dtype=np.float32)))


def test_bucketed_parity():
    ref_p, ref_s = run_steps("fused_allgather", ("data",), TREE_SIZES)
    # ~40 KB budget: the big leaf's ~168 KB message overflows it alone
    # (singleton bucket) and the two mid messages split across buckets
    got_p, got_s = run_steps("bucketed_allgather", ("data",), TREE_SIZES,
                             bucket_bytes=40_000)
    check_bitwise("bucketed == fused params (mixed tree, 8 workers)",
                  got_p, ref_p)
    check_bitwise("bucketed == fused state (mixed tree, 8 workers)",
                  got_s, ref_s)


def test_hierarchical_parity():
    axes = ("node", "local")
    ref_p, ref_s = run_steps("fused_allgather", axes, TREE_SIZES)
    got_p, got_s = run_steps("hierarchical", axes, TREE_SIZES)
    check_bitwise("hierarchical == fused params (2x4 node mesh)",
                  got_p, ref_p)
    check_bitwise("hierarchical == fused state (2x4 node mesh)",
                  got_s, ref_s)
    # non-default intra hop: intra-node psum over the FIRST sync axis;
    # the gathered rows must be transposed back to sync_axes-major order,
    # so parity still holds bitwise
    got_p, got_s = run_steps("hierarchical", axes, TREE_SIZES,
                             intra_axis="node")
    check_bitwise("hierarchical(intra=node) == fused params",
                  got_p, ref_p)
    check_bitwise("hierarchical(intra=node) == fused state",
                  got_s, ref_s)


def test_single_leaf():
    ref_p, ref_s = run_steps("fused_allgather", ("data",), SINGLE_SIZES)
    got_p, _ = run_steps("bucketed_allgather", ("data",), SINGLE_SIZES,
                         bucket_bytes=40_000)
    check_bitwise("bucketed == fused params (single-leaf model)",
                  got_p, ref_p)
    ref2_p, _ = run_steps("fused_allgather", ("node", "local"), SINGLE_SIZES)
    got2_p, _ = run_steps("hierarchical", ("node", "local"), SINGLE_SIZES)
    check_bitwise("hierarchical == fused params (single-leaf model)",
                  got2_p, ref2_p)


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    fns = {"order": test_row_order,
           "bucketed": test_bucketed_parity,
           "hierarchical": test_hierarchical_parity,
           "single": test_single_leaf}
    if which == "all":
        for fn in fns.values():
            fn()
    else:
        fns[which]()
    print("OK")
