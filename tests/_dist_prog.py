"""Multi-device correctness program, run as a subprocess by
test_distributed.py with XLA_FLAGS=--xla_force_host_platform_device_count=8
(the flag must be set before jax init, so it cannot run inside the main
pytest process).

Checks, on a (4 data x 2 model) mesh:
 1. sparse sync equivalence — RGC at density 1.0 (dense sentinel) matches
    single-device SGD on the concatenated global batch, bitwise-ish.
 2. RGC sparse update correctness — the multi-worker sparse allgather sum
    equals an oracle computed from each worker's local top-k.
 3. quantized + momentum variants run and stay finite.
"""
import sys

from harness.cluster import check, force_host_devices

force_host_devices(8)

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import TrainConfig, get_config
from repro.core.rgc import RGCConfig, rgc_apply, rgc_init
from repro.core import selection as sel
from repro.data import bigram_batches
from repro.launch.mesh import make_host_mesh
from repro.train.trainer import Trainer, make_rgc_config, make_train_step
from repro.models.registry import get_model


def test_dense_equivalence():
    """density=1.0 multi-worker == single-device big-batch SGD."""
    cfg = get_config("internlm2-1.8b", smoke=True)
    model = get_model(cfg)
    tc = TrainConfig(lr=0.1, momentum=0.9, optimizer="dense")
    mesh = make_host_mesh(4, 2)

    params = model.init_params(0)
    batch = model.make_train_batch(8, 32)

    # multi-device
    step = make_train_step(model, mesh, None, tc, donate=False)
    st = rgc_init(params, make_rgc_config(tc, mesh))
    loss_m, p_m, _ = step(params, st, batch, jnp.float32(0.1))

    # single device oracle
    step1 = make_train_step(model, None, None, tc, donate=False)
    st1 = rgc_init(params, make_rgc_config(tc, None))
    loss_1, p_1, _ = step1(params, st1, batch, jnp.float32(0.1))

    check("dense loss match",
          abs(float(loss_m) - float(loss_1)) < 1e-4 * max(1, abs(float(loss_1))))
    errs = [float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                  - b.astype(jnp.float32))))
            for a, b in zip(jax.tree.leaves(p_m), jax.tree.leaves(p_1))]
    check(f"dense params match (max err {max(errs):.2e})", max(errs) < 5e-3)


def test_sparse_allgather_oracle():
    """Each of the 4 data workers compresses a DIFFERENT local gradient;
    the decompressed sum must equal the sum of each worker's top-k
    contribution (computed with the pure selector as oracle)."""
    mesh = make_host_mesh(4, 1)
    n, k_density = 4000, 0.01
    rng = np.random.default_rng(0)
    grads_per_worker = rng.standard_normal((4, n)).astype(np.float32)
    params = jnp.zeros((n,), jnp.float32)
    cfg = RGCConfig(density=k_density, momentum=0.0, sync_axes=("data",),
                    dense_threshold_bytes=64)

    from jax.sharding import PartitionSpec as P

    def worker(g, p, st):
        new_p, new_st = rgc_apply({"w": g}, {"w": p}, {"w": st},
                                  lr=jnp.float32(1.0), cfg=cfg)
        return new_p["w"], new_st["w"]

    st0 = rgc_init({"w": params}, cfg)["w"]
    f = jax.jit(jax.shard_map(
        worker, mesh=mesh,
        in_specs=(P("data"), P(), jax.tree.map(lambda _: P(), st0)),
        out_specs=(P(), jax.tree.map(lambda _: P(), st0)),
        check_vma=False))
    new_p, _ = f(jnp.asarray(grads_per_worker), params, st0)

    # oracle: sum of each worker's selected top-k, averaged over 4
    k = max(1, int(np.ceil(k_density * n)))
    expect = np.zeros(n, np.float32)
    for w in range(4):
        s = sel.trimmed_topk(jnp.asarray(grads_per_worker[w]), k)
        cnt = int(s.count)
        np.add.at(expect, np.asarray(s.indices)[:cnt],
                  np.asarray(s.values)[:cnt])
    expect /= 4.0
    err = np.max(np.abs(np.asarray(new_p) + expect))   # lr=1 -> p = -upd
    check(f"sparse allgather oracle (err {err:.2e})", err < 1e-5)


def test_variants_run():
    mesh = make_host_mesh(4, 2)
    cfg = get_config("granite-moe-3b-a800m", smoke=True)
    for opt in ("rgc", "rgc_quant"):
        tc = TrainConfig(lr=0.2, density=0.02, optimizer=opt,
                         local_clip=1.0)
        tr = Trainer(cfg, tc, mesh=mesh)
        st = tr.init_state()
        st = tr.run(st, bigram_batches(cfg.vocab_size, 8, 32, seed=0), 5,
                    log_every=0)
        finite = all(np.isfinite(np.asarray(l, np.float32)).all()
                     for l in jax.tree.leaves(st.params))
        check(f"{opt} 5 steps finite on mesh", finite)


def test_multipod_axes():
    """3-axis mesh ('pod','data','model'): RGC syncs over ('pod','data')."""
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    cfg = get_config("internlm2-1.8b", smoke=True)
    tc = TrainConfig(lr=0.2, density=0.02, optimizer="rgc")
    tr = Trainer(cfg, tc, mesh=mesh)
    st = tr.init_state()
    st = tr.run(st, bigram_batches(cfg.vocab_size, 8, 32, seed=0), 3,
                log_every=0)
    finite = all(np.isfinite(np.asarray(l, np.float32)).all()
                 for l in jax.tree.leaves(st.params))
    check("multi-pod axes RGC finite", finite)


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    fns = {"dense": test_dense_equivalence,
           "oracle": test_sparse_allgather_oracle,
           "variants": test_variants_run,
           "multipod": test_multipod_axes}
    if which == "all":
        for f in fns.values():
            f()
    else:
        fns[which]()
    print("OK")
