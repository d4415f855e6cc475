"""Share of selection's per-slot ``conditional`` ops in the traced steps
that ran the exact fallback (an op under the ``fallback`` scope) because
the slot's buckets overflowed (``chipbench.scopes``)."""


def read(rec):
    share = (rec.get("trace") or {}).get("fallback_share")
    return None if share is None else 100.0 * share
