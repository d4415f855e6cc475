"""Peak device memory in use (``peak_bytes_in_use``) of the fullest chip
after the window, in GB (1e9 bytes)."""


def read(rec):
    b = rec.get("peak_bytes")
    return None if b is None else b / 1e9
