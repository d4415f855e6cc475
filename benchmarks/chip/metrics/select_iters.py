"""Iterations per step of selection's bisection loop in the traced
steps: executions of its survivor count, the ops under the ``search``
scope (``chipbench.scopes``)."""


def read(rec):
    return (rec.get("trace") or {}).get("search_iters")
