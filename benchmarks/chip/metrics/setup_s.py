"""Set-up: process start to the first timed step (imports, reaching the
chips, weights, traffic, compiling or loading the step, first steps)."""


def read(rec):
    return rec.get("setup_s")
