"""Largest ``peak_bytes_in_use`` over the chips, read after the window, in
GB (1e9 bytes)."""


def read(rec):
    peak = rec["memory_peak_bytes"]
    return peak / 1e9 if peak else None
