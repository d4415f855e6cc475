"""Device time per step of selection's overflow fallbacks: the exact
selections under the program's ``sync/select/fallback`` scope that slots
ran when their buckets overflowed, in the traced steps
(``chipbench.scopes``). Zero when selection ran and no slot fell back."""
from chipbench.scopes import scope_ms


def read(rec):
    if scope_ms(rec, "sync/select") is None:
        return None
    return scope_ms(rec, "sync/select/fallback") or 0.0
