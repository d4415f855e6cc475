"""Device time per step of the gradient sync inside the training step:
its ops under the program's ``sync`` scope in the traced steps
(``chipbench.scopes``)."""
from chipbench.scopes import scope_ms


def read(rec):
    return scope_ms(rec, "sync")
