"""Device time per call of ``jit(value_and_grad(model.loss))`` on one
chip's batch, called alone by the benchmark (``jit_bench_fwd_bwd``)."""
from chipbench.tracing import per_call_s


def read(rec):
    s = per_call_s(rec, "jit_bench_fwd_bwd")
    return None if s is None else 1e3 * s
