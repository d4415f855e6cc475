"""Share of the chip's bf16 peak that the model's forward and backward
pass reaches alone: the FLOPs of one chip's batch (``flops_per_token``
times ``tokens_per_step / chips``, recomputation not counted, as for
``mfu``) over the device time per call of the benchmark's own
``jit_bench_fwd_bwd``, over ``bf16_flops_per_s``. Bound by compute: a
matmul-bound pass reads its weights and activations in far less time
than its FLOPs take."""
from chipbench.tracing import per_call_s


def read(rec):
    s = per_call_s(rec, "jit_bench_fwd_bwd")
    peaks = rec.get("peaks")
    if s is None or not peaks:
        return None
    flops = rec["flops_per_token"] * rec["tokens_per_step"] / rec["chips"]
    return 100.0 * flops / s / peaks["bf16_flops_per_s"]
