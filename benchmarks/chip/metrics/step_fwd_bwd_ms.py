"""Device time per step of the model's forward and backward inside the
training step: its ops under the program's ``fwd_bwd`` scope in the
traced steps (``chipbench.scopes``)."""
from chipbench.scopes import scope_ms


def read(rec):
    return scope_ms(rec, "fwd_bwd")
