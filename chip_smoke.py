#!/usr/bin/env python3
"""Start-up check of the RedSync trainer on TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the four-chip data-parallel phase only
    python chip_smoke.py --rehearse [--chips 4]

One chip: the paper's 2x1500 LSTM (``paper-lstm``, PTB widths, random
weights from ``--seed``) trains a few steps at the paper's PTB batch
(20 x 35 per chip) through ``Trainer`` in three arms — RGC
``momentum+clip(threshold_bsearch)`` at density 0.01 with the jnp and
with the Pallas selection kernels, and dense SGD — and the segmented
Pallas kernels are compared with their jnp twins on the chip.

Four chips: the same model on the launcher's pure data-parallel
``("data",)`` mesh. Dense over four chips is compared with dense on one
chip over the same global batch, RGC (``fused_allgather``) over four
chips with the same job on four host CPU devices (the simulated-cluster
path), and each worker's residual must stay its own.

A passing run prints, as its last line,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
A failed check, or a machine without a TPU, exits non-zero without that
line. ``--rehearse`` runs the same control flow on CPU at the smoke
config (kernels interpreted) and never prints it.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys
import time
from collections import Counter

HERE = os.path.dirname(os.path.abspath(__file__))
PER_CHIP_BATCH, SEQ = 20, 35      # the paper's PTB setting
DENSITY = 0.01
RGC = "momentum+clip(threshold_bsearch)"
STEPS = 3
# Agreement bounds. Losses relative to the loss; updates as
# ||dA - dB|| / ||dB|| with d = params after STEPS steps - initial params
# (at init the loss moves by ~1e-4 a step, so the update is the sharper
# test of the sync).
STEP0_RTOL = 1e-5          # 1 chip: the first loss vs model.loss
DENSE_RTOL = 1e-4          # dense, 4 chips vs 1 chip: reduction order
DENSE_UPDATE_RTOL = 1e-3
XDEV_STEP0_RTOL = 1e-4     # chips vs host CPU: f32 matmuls on both, but
RGC_RTOL = 1e-3            # transcendentals differ by ulps and a
RGC_UPDATE_RTOL = 1e-2     # near-tie coordinate may select differently


def check(name: str, ok: bool, detail: str = "") -> None:
    print(f"{'PASS' if ok else 'FAIL'} {name}  {detail}".rstrip(), flush=True)
    if not ok:
        raise SystemExit(1)


def rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def batches(cfg, global_batch: int, seed: int, n: int) -> list[dict]:
    from repro.data import bigram_batches
    src = bigram_batches(cfg.vocab_size, global_batch, SEQ, seed=seed)
    return [next(src) for _ in range(n)]


def train(cfg, tc, mesh, data, seed: int, report: str):
    """Train STEPS steps through ``Trainer``; returns (losses, params
    before and after the first step, final state, compiled step)."""
    import jax
    import jax.numpy as jnp

    from repro.train.trainer import Trainer
    trainer = Trainer(cfg, tc, mesh=mesh)
    state = trainer.init_state(seed)
    params0 = state.params
    batch0 = {k: jnp.asarray(v) for k, v in data[0].items()}
    # compile the trainer's own step ahead of its first call (same
    # program, so ``run`` reuses it) to time it and read what it holds
    step_fn = trainer._step_fn(trainer.density_at(0))
    t0 = time.perf_counter()
    compiled = step_fn.lower(state.params, state.rgc, batch0,
                             jnp.float32(tc.lr)).compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    print(f"[{report}] compile {compile_s:.2f} s  memory_analysis: "
          f"arguments {mem.argument_size_in_bytes} B, outputs "
          f"{mem.output_size_in_bytes} B, temporaries "
          f"{mem.temp_size_in_bytes} B, code "
          f"{mem.generated_code_size_in_bytes} B", flush=True)
    losses: list[float] = []

    def on_metrics(step, density, loss):
        losses.append(loss)

    state = trainer.run(state, iter(data[:1]), 1, log_every=0,
                        on_metrics=on_metrics)
    params1 = state.params
    state = trainer.run(state, iter(data[1:]), STEPS - 1, log_every=0,
                        on_metrics=on_metrics)
    jax.block_until_ready(state.params)
    print(f"[{report}] losses {losses}", flush=True)
    return losses, params0, params1, state, compiled


def update_gap(run_a, run_b) -> float:
    """||dA - dB|| / ||dB|| over every parameter, d = final - initial
    params of each ``train`` result."""
    import jax
    import numpy as np
    num = den = 0.0
    for a0, a1, b0, b1 in zip(*(jax.tree.leaves(t) for t in (
            run_a[1], run_a[3].params, run_b[1], run_b[3].params))):
        da = np.asarray(a1, np.float64) - np.asarray(a0, np.float64)
        db = np.asarray(b1, np.float64) - np.asarray(b0, np.float64)
        num += float(np.sum((da - db) ** 2))
        den += float(np.sum(db ** 2))
    return math.sqrt(num / den)


def changed_coords(before, after) -> int:
    import jax
    import jax.numpy as jnp
    return int(sum(jnp.sum(a != b) for a, b in
                   zip(jax.tree.leaves(before), jax.tree.leaves(after))))


def peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def kernel_parity(seed: int) -> None:
    """The segmented kernels against their jnp twins on one arena whose
    row blocks straddle slots and end in a partial block."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import arena
    from repro.kernels import ref
    from repro.kernels import segmented as kseg

    sizes = [300_001, 1023, 5000, 70_000]
    group = arena.build_group(
        0, "threshold_bsearch", "float32",
        [(i, f"slot{i}", n, n // 100 + 1, 2 * (n // 100 + 1), 1)
         for i, n in enumerate(sizes)])
    g = group.geometry
    rng = np.random.default_rng(seed)
    x2d = arena.gather(group, [jnp.asarray(rng.standard_normal(n),
                                           jnp.float32) for n in sizes])
    thr = jnp.asarray([2.0, 0.5, 1.5, 2.5], jnp.float32)
    stride_b = np.full(g.nblocks, 4, np.int32)

    s, m = kseg.seg_abs_sum_max(x2d, g.block_seg, g.n_seg)
    s_ref, m_ref = ref.seg_abs_sum_max(x2d, g.block_seg, g.block_size,
                                       g.n_seg)
    check("kernel seg_abs_sum_max",
          np.allclose(s, s_ref, rtol=1e-5) and np.array_equal(m, m_ref),
          f"sums {np.asarray(s)} vs {np.asarray(s_ref)}")
    s4, m4 = kseg.seg_abs_sum_max(x2d, g.block_seg, g.n_seg,
                                  stride_b=stride_b)
    s4_ref, m4_ref = ref.seg_abs_sum_max(x2d, g.block_seg, g.block_size,
                                         g.n_seg, (4,) * g.n_seg)
    check("kernel seg_abs_sum_max strided",
          np.allclose(s4, s4_ref, rtol=1e-5)
          and np.array_equal(m4, m4_ref))
    c = kseg.seg_count_gt(x2d, g.block_seg, thr)
    c_ref = ref.seg_count_gt(x2d, g.block_seg, thr, g.n_seg)
    check("kernel seg_count_gt", np.array_equal(c, c_ref),
          f"{np.asarray(c)} vs {np.asarray(c_ref)}")
    c4 = kseg.seg_count_gt(x2d, g.block_seg, thr, stride_b=stride_b)
    c4_ref = ref.seg_count_gt(x2d, g.block_seg, thr, g.n_seg, stride_b)
    check("kernel seg_count_gt strided", np.array_equal(c4, c4_ref))
    got = kseg.seg_compact_gt(x2d, g.block_seg, g.block_base, g.block_size,
                              thr, 88)
    want = ref.seg_compact_gt(x2d, g.block_seg, g.block_base, g.block_size,
                              thr, 88)
    check("kernel seg_compact_gt",
          all(np.array_equal(a, b) for a, b in zip(got, want)))
    u2d = x2d[::-1]
    got = kseg.seg_residual_update_stats(
        x2d * 0.5, x2d, u2d, None, g.block_seg, g.n_seg, momentum=0.9,
        nesterov=True)
    want = ref.seg_residual_update_stats(
        x2d * 0.5, x2d, u2d, None, g.block_seg, g.n_seg, momentum=0.9,
        nesterov=True)
    check("kernel seg_residual_update_stats",
          all(np.allclose(a, b, rtol=1e-5, atol=1e-6)
              for a, b in zip(got, want)))


def one_chip(cfg, seed: int, rehearse: bool) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs import TrainConfig
    from repro.models.registry import get_model
    dev = jax.devices()[0]
    kernel_parity(seed)
    data = batches(cfg, PER_CHIP_BATCH, seed, STEPS)
    model = get_model(cfg)
    ref_loss = float(jax.jit(model.loss)(
        model.init_params(seed),
        {k: jnp.asarray(v) for k, v in data[0].items()}))
    arms = {
        "rgc-jnp": TrainConfig(optimizer=RGC, density=DENSITY),
        "rgc-pallas": TrainConfig(optimizer=RGC, density=DENSITY,
                                  backend="pallas"),
        "dense": TrainConfig(optimizer="dense", transport="dense_psum"),
    }
    out = {}
    for name, tc in arms.items():
        losses, p0, p1, state, compiled = train(cfg, tc, None, data, seed,
                                                name)
        out[name] = (losses, changed_coords(p0, p1))
        print(f"[{name}] coordinates changed by step 1: {out[name][1]}  "
              f"peak_bytes_in_use {peak_bytes(dev)}", flush=True)
        if name == "rgc-pallas":
            has_kernel = "tpu_custom_call" in compiled.as_text()
            if rehearse:
                print(f"[rehearse] tpu_custom_call in step: {has_kernel} "
                      f"(kernels are interpreted on CPU)")
            else:
                check("pallas step holds tpu_custom_call", has_kernel)
        del state, p0, p1, compiled

    first = {name: o[0][0] for name, o in out.items()}
    check("step-0 loss identical across arms",
          len(set(first.values())) == 1, str(first))
    check("step-0 loss equals model.loss",
          rel(first["rgc-jnp"], ref_loss) <= STEP0_RTOL,
          f"{first['rgc-jnp']} vs {ref_loss}")
    check("every loss finite",
          all(math.isfinite(l) for o in out.values() for l in o[0]))
    n_jnp, n_pallas = out["rgc-jnp"][1], out["rgc-pallas"][1]
    check("jnp and pallas select the same coordinates count at step 1",
          n_jnp == n_pallas, f"{n_jnp} vs {n_pallas}")


def collectives(text: str) -> Counter:
    return Counter(re.findall(
        r"\s(all-gather|all-reduce|reduce-scatter|collective-permute|"
        r"all-to-all)(?:-start)?\(", text))


def residual_copies(state) -> list:
    """Each worker's copy of the largest residual leaf."""
    import jax
    import numpy as np
    leaves = [s.residual for s in jax.tree.leaves(
        state.rgc, is_leaf=lambda x: hasattr(x, "residual"))]
    big = max(leaves, key=lambda a: a.size)
    return [np.asarray(s.data) for s in big.addressable_shards]


def four_chip(cfg, seed: int) -> None:
    import jax
    import numpy as np

    from repro.configs import TrainConfig
    from repro.launch.mesh import mesh_from_spec
    mesh = mesh_from_spec("4x1")
    check("launcher mesh is the pure ('data',) mesh over 4 devices",
          mesh.axis_names == ("data",) and mesh.devices.size == 4,
          str(mesh))
    data = batches(cfg, 4 * PER_CHIP_BATCH, seed, STEPS)

    dense = TrainConfig(lr=1.0, optimizer="dense", transport="dense_psum")
    d4 = train(cfg, dense, mesh, data, seed, "dense x4")
    d1 = train(cfg, dense, None, data, seed, "dense x1")
    check("dense: 4 devices == 1 device on the same global batch (loss)",
          all(rel(a, b) <= DENSE_RTOL for a, b in zip(d4[0], d1[0])),
          f"{d4[0]} vs {d1[0]}")
    gap = update_gap(d4, d1)
    check("dense: 4 devices == 1 device (update)",
          gap <= DENSE_UPDATE_RTOL, f"gap {gap}")
    del d4, d1

    rgc = TrainConfig(lr=1.0, optimizer=RGC, density=DENSITY,
                      transport="fused_allgather")
    cpus = jax.devices("cpu")[:4]
    cpu_mesh = jax.make_mesh(
        (4,), ("data",), devices=cpus,
        axis_types=(jax.sharding.AxisType.Auto,))
    with jax.default_matmul_precision("highest"):
        on_chips = train(cfg, rgc, mesh, data, seed, "rgc x4")
        with jax.default_device(cpus[0]):
            on_cpus = train(cfg, rgc, cpu_mesh, data, seed,
                            "rgc x4 host cpu")
    r4, state, compiled = on_chips[0], on_chips[3], on_chips[4]
    rc = on_cpus[0]
    print(f"[rgc x4] collectives in the compiled step: "
          f"{dict(collectives(compiled.as_text()))}", flush=True)
    for line in compiled.as_text().splitlines():
        if re.search(r"\s(all-gather|all-reduce)(-start)?\(", line):
            print("   ", line.strip()[:200])
    check("rgc: first loss equal on chips and host CPU devices",
          rel(r4[0], rc[0]) <= XDEV_STEP0_RTOL, f"{r4[0]} vs {rc[0]}")
    check("rgc: 4 chips == 4 host CPU devices (loss)",
          all(rel(a, b) <= RGC_RTOL for a, b in zip(r4, rc)),
          f"{r4} vs {rc}")
    gap = update_gap(on_chips, on_cpus)
    check("rgc: 4 chips == 4 host CPU devices (update)",
          gap <= RGC_UPDATE_RTOL, f"gap {gap}")
    copies = residual_copies(state)
    distinct = all(not np.array_equal(copies[i], copies[j])
                   for i in range(4) for j in range(i + 1, 4))
    check("per-worker residuals stay distinct after the steps",
          len(copies) == 4 and distinct,
          f"{len(copies)} copies, nonzero "
          f"{[int(np.count_nonzero(c)) for c in copies]}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the smoke config; never prints "
                    "the result line")
    args = ap.parse_args()
    if args.chips == 4:
        # four host CPU devices for the simulated-cluster comparison
        # (and, when rehearsing, for the "chips" too); set before jax
        # initializes its backends
        os.environ["XLA_FLAGS"] = " ".join(filter(None, [
            os.environ.get("XLA_FLAGS"),
            "--xla_force_host_platform_device_count=4"]))
    sys.path.insert(0, os.path.join(HERE, "src"))
    import jax

    from repro.configs import get_config
    from repro.launch.cache import use_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU (JAX sees {dev.platform}); failing",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    cache = use_compile_cache()
    print(f"device {dev.platform} {dev.device_kind} x{len(devices)}  "
          f"jax {jax.__version__}  compile cache {cache}", flush=True)
    cfg = get_config("paper-lstm", smoke=args.rehearse)
    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip(cfg, args.seed, args.rehearse)
    else:
        four_chip(cfg, args.seed)
    print(f"all checks passed in {time.perf_counter() - t0:.1f} s",
          flush=True)
    if not args.rehearse:
        print(json.dumps({"ok": True, "device": {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
