"""Largest ``peak_bytes_in_use`` over the cell's devices after the
window, in GB (10**9 bytes)."""


def read(run):
    return max(run.peak_bytes) / 1e9 if any(run.peak_bytes) else None
