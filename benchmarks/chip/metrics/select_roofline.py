"""Share of its roofline that the arena path's fused selection reaches
(``jit_bench_select``): the least time, the arenas' float32 bytes read
once plus 8 bytes for each of the ``k`` coordinates a slot must select,
over the HBM bandwidth, divided by the device time per call. The call
mix is the step's (one fresh search per ``interval`` calls). Bound by
memory: selection does no matmul work."""
from chipbench.tracing import per_call_s


def read(rec):
    s = per_call_s(rec, "jit_bench_select")
    peaks = rec.get("peaks")
    if s is None or not peaks or not rec.get("select_bytes"):
        return None
    return 100.0 * rec["select_bytes"] / peaks["hbm_bytes_per_s"] / s
