"""Pieces the kinds share for deciding ``correct``: the lower-precision
control and the error ratio compared with a limit.
"""
from __future__ import annotations

import numpy as np

# Divisor floor for entries whose |A| @ |B| is 0: far below any real
# entry's (a product of two float32 weights that are not 0 is above
# 2**-300), so a nonzero there reads as a huge but finite ratio.
_TINY = 2.0 ** -300

# Rows of the left operand per device call of the control.
_PANEL_ROWS = 4096


def to_host(arrays: dict) -> dict:
    """Device inputs copied to the host as float64, for the reference."""
    return {k: np.asarray(v, dtype=np.float64) for k, v in arrays.items()}


def _dense_f32(x) -> np.ndarray:
    if hasattr(x, "tocoo"):
        coo = x.tocoo()
        out = np.zeros(coo.shape, np.float32)
        out[coo.row, coo.col] = coo.data
        return out
    return np.asarray(x, dtype=np.float32)


def _split_bf16(x: np.ndarray) -> tuple:
    """``(hi, lo)`` in bfloat16 with ``hi`` the nearest bfloat16 to ``x``
    and ``lo`` the nearest to ``x - hi``, rounded on the host, where no
    compiler may drop a rounding as excess precision."""
    import ml_dtypes

    hi = x.astype(ml_dtypes.bfloat16)
    lo = np.empty_like(hi)
    for r in range(0, x.shape[0], _PANEL_ROWS):    # float32 temporaries
        rows = slice(r, r + _PANEL_ROWS)           # a panel at a time
        lo[rows] = (x[rows] - hi[rows].astype(np.float32)).astype(
            ml_dtypes.bfloat16)
    return hi, lo


def high_dense_matmul(a, b) -> np.ndarray:
    """``a @ b`` at JAX's ``high`` precision, three bfloat16 passes, on the
    default device: each float32 operand split into a bfloat16 ``hi`` and
    ``lo`` part, ``hi·hi + hi·lo + lo·hi`` summed in float32, ``lo·lo``
    and what ``lo`` leaves out dropped.  The passes are written out, so the
    control computes the same on every backend.  ``a`` and ``b`` are scipy
    sparse matrices or arrays; returns float32 on the host."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def passes(ah, al, bh, bl):
        def dot(x, y):
            return jnp.dot(x, y, preferred_element_type=jnp.float32)
        return dot(ah, bh) + dot(ah, bl) + dot(al, bh)

    ah, al = _split_bf16(_dense_f32(a))
    bh, bl = (jnp.asarray(p) for p in (
        (ah, al) if b is a else _split_bf16(_dense_f32(b))))
    out = np.empty((ah.shape[0], bh.shape[1]), np.float32)
    for r in range(0, ah.shape[0], _PANEL_ROWS):
        rows = slice(r, r + _PANEL_ROWS)
        out[rows] = np.asarray(passes(jnp.asarray(ah[rows]),
                                      jnp.asarray(al[rows]), bh, bl))
    return out


def max_err_ratio(got, want, absprod) -> float:
    """The largest ``|got - want| / absprod`` over all entries, where
    ``absprod`` is ``|A| @ |B|`` at the entry; 0 for no entries."""
    got = np.asarray(got, dtype=np.float64)
    if got.size == 0:
        return 0.0
    err = np.abs(got - np.asarray(want, dtype=np.float64))
    return float((err / np.maximum(np.asarray(absprod, np.float64),
                                   _TINY)).max())


def verdict(readings: dict, limits: dict) -> tuple:
    """``(correct, checks)``: each reading beside its limit, in the order
    of the limits file; a reading that is missing or over its limit makes
    the run incorrect."""
    checks = {}
    correct = True
    for name, spec in limits.items():
        value = readings.get(name)
        limit = spec["limit"]
        checks[name] = {"value": value, "limit": limit}
        if value is None or not value <= limit:
            correct = False
    return correct, checks
