"""Device time per step of the sync pipeline: the training step's device
time per execution in the traced steps, less the device time of one call
of the model's forward and backward on the same chip's batch
(``jit_bench_fwd_bwd``). What is left is accumulate, select, mask, pack,
exchange and apply, and the step's small remainder."""
from chipbench.tracing import per_call_s


def read(rec):
    trace = rec.get("trace")
    fwd_bwd = per_call_s(rec, "jit_bench_fwd_bwd")
    if not trace or not trace.get("step_programs") or fwd_bwd is None:
        return None
    return 1e3 * (trace["step_s"] / trace["step_programs"] - fwd_bwd)
