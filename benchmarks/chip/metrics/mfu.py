"""Model FLOP/s utilisation of the whole step: the forward and backward
FLOPs a token needs (``chipref``'s ``flops_per_token``, recomputation not
counted) times tokens per second, over chips times the chip's bf16 peak.
Float32 matmuls run as bf16 passes at default precision, so the bf16
peak serves every configuration."""


def read(rec):
    peaks = rec.get("peaks")
    if not peaks or not rec.get("window_s"):
        return None
    rate = rec["tokens"] / rec["window_s"]
    return 100.0 * rec["flops_per_token"] * rate / (
        rec["chips"] * peaks["bf16_flops_per_s"])
