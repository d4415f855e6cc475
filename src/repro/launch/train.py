"""Training launcher.

On TPU it runs on ``jax.devices()``: one chip without ``--mesh``, the
pure data-parallel ``("data",)`` mesh with ``--mesh Dx1``, a ("data",
"model") mesh with ``--mesh DxM``, the production meshes with ``pod`` /
``2pod``. On CPU it drives the same code path at smoke scale
(``--smoke`` configs, forced host devices via ``REPRO_HOST_DEVICES``,
appended to ``XLA_FLAGS`` before jax initializes its backends).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b --smoke \
      --steps 50 --batch 8 --seq 128 --optimizer rgc --density 0.01
  REPRO_HOST_DEVICES=8 PYTHONPATH=src python -m repro.launch.train \
      --arch rwkv6-3b --smoke --mesh 4x2 --steps 20
"""
import argparse
import os

import jax

from repro.configs import ARCH_IDS, TrainConfig, get_config
from repro.data import SyntheticLM, bigram_batches
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import mesh_from_spec
from repro.train.trainer import Trainer


def main() -> None:
    if os.environ.get("REPRO_HOST_DEVICES"):
        os.environ["XLA_FLAGS"] = " ".join(filter(None, [
            os.environ.get("XLA_FLAGS"),
            "--xla_force_host_platform_device_count="
            + os.environ["REPRO_HOST_DEVICES"]]))
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS + ("paper-lstm",),
                    required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=0.2)
    ap.add_argument("--optimizer", default="rgc",
                    help="rgc | rgc_quant | dense | any registered "
                    "compressor spec, e.g. threshold_bsearch or "
                    "'quantized(trimmed_topk)'")
    from repro.core import registry
    ap.add_argument("--transport", default="fused_allgather",
                    choices=list(registry.names(registry.TRANSPORT)))
    ap.add_argument("--bucket-bytes", type=int, default=None,
                    help="bucketed_allgather: byte budget per fused "
                    "collective bucket (default 4 MiB)")
    ap.add_argument("--no-fuse-leaves", action="store_true",
                    help="disable the flat residual arenas (per-leaf "
                    "mask/select/pack baseline)")
    ap.add_argument("--schedule", default="sequential",
                    help="§5.6 overlap scheduler spec: sequential (one "
                    "full-tree transport barrier), chunked (pipelined "
                    "per-chunk dispatch in reverse parameter order, "
                    "bitwise-identical results), stale1 (one-step-"
                    "delayed double-buffered sync), or a parameterized "
                    "spec like 'staleK(k=4)' (K-step-delayed ring)")
    ap.add_argument("--backend", default=None, choices=["jnp", "pallas"],
                    help="selection-kernel backend (pallas compiles on "
                    "TPU, interprets on CPU)")
    ap.add_argument("--density", type=float, default=0.01)
    ap.add_argument("--momentum", type=float, default=0.9)
    ap.add_argument("--warmup-steps-per-stage", type=int, default=0)
    ap.add_argument("--mesh", default=None,
                    help="DxM over the devices (e.g. 4x2); Dx1 is the "
                    "pure data-parallel ('data',) mesh; 'pod' or "
                    "'2pod' for the production meshes")
    ap.add_argument("--data", default="bigram", choices=["bigram", "zipf"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="save a fault-tolerance checkpoint (full + "
                    "sparse-delta chain) every N steps into --ckpt-dir")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest checkpoint in "
                    "--ckpt-dir (bitwise at the same device count; "
                    "elastic remainder-rule redistribution otherwise)")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    # validate the schedule spec up front (free-form grammar, so argparse
    # choices can't) — unknown names / malformed kwargs fail here
    from repro.core.overlap import make_schedule
    make_schedule(args.schedule)

    use_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    mesh = mesh_from_spec(args.mesh)

    tc = TrainConfig(lr=args.lr, momentum=args.momentum,
                     optimizer=args.optimizer, transport=args.transport,
                     schedule=args.schedule, density=args.density,
                     warmup_steps_per_stage=args.warmup_steps_per_stage,
                     fuse_leaves=not args.no_fuse_leaves)
    overrides = {}
    if args.bucket_bytes is not None:
        overrides["bucket_bytes"] = args.bucket_bytes
    if args.backend is not None:
        overrides["backend"] = args.backend
    if overrides:
        import dataclasses
        tc = dataclasses.replace(tc, **overrides)
    trainer = Trainer(cfg, tc, mesh=mesh, ckpt_dir=args.ckpt_dir,
                      ckpt_every=args.ckpt_every)
    resume_from = 0
    if args.resume:
        if not args.ckpt_dir:
            raise SystemExit("--resume requires --ckpt-dir")
        state = trainer.restore_checkpoint()
        resume_from = state.step
        print(f"resumed from step {resume_from} in {args.ckpt_dir}")
    else:
        state = trainer.init_state()
    n = sum(x.size for x in jax.tree.leaves(state.params))
    print(f"arch={cfg.name} params={n:,} optimizer={args.optimizer} "
          f"density={args.density} mesh={args.mesh or 'single-device'}")

    if args.data == "bigram":
        batches = bigram_batches(cfg.vocab_size, args.batch, args.seq,
                                 seed=tc.seed)
    else:
        batches = iter(SyntheticLM(cfg.vocab_size, args.batch, args.seq,
                                   seed=tc.seed))
    if cfg.family in ("vlm", "encdec"):
        # modality stubs: attach frame/patch embeddings to each batch
        from repro.models.registry import get_model
        model = get_model(cfg)
        stub = model.make_train_batch(args.batch, args.seq)

        def with_stub(src):
            for b in src:
                extra = {k: v for k, v in stub.items() if k != "tokens"}
                yield {**b, **extra}
        batches = with_stub(batches)

    if resume_from:
        batches = iter(batches)
        for _ in range(resume_from):        # deterministic batch replay
            next(batches)
    trainer.run(state, batches, max(0, args.steps - resume_from),
                log_every=args.log_every)


if __name__ == "__main__":
    main()
