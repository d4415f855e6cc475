"""Device time per step of selection inside the training step: its ops
under the program's ``sync/select`` scope in the traced steps, the
search loop and the overflow fallbacks included (``chipbench.scopes``)."""
from chipbench.scopes import scope_ms


def read(rec):
    return scope_ms(rec, "sync/select")
