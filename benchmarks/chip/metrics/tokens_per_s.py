"""Tokens trained on all chips in the window over the window's length."""


def read(rec):
    if not rec.get("window_s"):
        return None
    return rec["tokens"] / rec["window_s"]
