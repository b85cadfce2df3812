"""Sparse-output SpGEMM, ``C = A @ A``: the graph squared, kept sparse.

How a product is built from the handles a user makes, its plain
reference, the lower-precision control, the comparison that decides
``correct``, and the product's format-independent work.

Precision.  A is float32, its weights uniform on [0, 1), and the
configuration states float32 arithmetic (``matmul_precision``
``highest``, which the harness gives JAX before anything is traced).  The
program's pair-accumulate kernel calls ``jnp.dot`` with no precision of its
own, so it multiplies at that precision: float32 operands, sums in
float32, an error of some 2**-24 of the entry per term summed (every
weight is positive, so ``|A| @ |A|`` is C itself and nothing cancels).
The control is the next precision below, ``high``: three bfloat16 passes,
which leave out what lies beyond 16 significant bits of each weight, some
2**-17 to 2**-16 of a product.  The program's own default, one bfloat16
pass (operands rounded to 8 significant bits, as a program that stored A
in bfloat16 would), errs by up to some 2**-8 and fails by far more.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sps

from harness import check


def inputs(csr: sps.csr_matrix, traffic: dict, seed: int) -> dict:
    """Nothing besides A."""
    return {}


class Product:
    """One cell's product, built through the calls a user makes."""

    def __init__(self, a_h, inputs: dict, traffic: dict):
        self.traffic = traffic
        self.args = (a_h, a_h)
        self.plan = None

    def build_plan(self, mesh, impl: str) -> None:
        from repro.core.api import plan_matmul

        self.plan = plan_matmul(*self.args, impl=impl, mesh=mesh,
                                **self.traffic["plan"])

    def __call__(self):
        return self.plan(*self.args)

    @staticmethod
    def ready(out):
        """The array to block on: the output handle's stored blocks."""
        return out.tiled.blocks


class TileView:
    """The program's sparse output, read on the host.

    The output handle stores, per tile ``(i, j)`` of a ``g x g`` grid,
    ``S`` slots of ``bs x bs`` blocks with their block row and column
    inside the tile.  Several slots may name one block position (padding
    and coverage slots); an entry's value is the sum over all of them, so
    the reading does not depend on which slot the program fills.
    """

    def __init__(self, out):
        t = out.tiled
        if t.row_block_perm is not None or t.col_block_perm is not None:
            raise ValueError("balanced outputs are not read by this view")
        self.bs = t.block_size
        g = t.grid_shape[0]
        nbr, nbc = t.tile_shape[0] // self.bs, t.tile_shape[1] // self.bs
        self.nb = g * nbc
        rows = np.asarray(t.rows, dtype=np.int64)
        cols = np.asarray(t.cols, dtype=np.int64)
        i = np.arange(g)[:, None, None]
        j = np.arange(g)[None, :, None]
        self.slot_key = ((i * nbr + rows) * self.nb + j * nbc + cols).ravel()
        self.blocks = np.asarray(t.blocks).reshape(-1, self.bs, self.bs)

    def values_at(self, r: np.ndarray, c: np.ndarray):
        """Float64 values at entries ``(r, c)`` and the number of nonzero
        stored values that lie outside them."""
        bs = self.bs
        ekey = (r // bs) * self.nb + c // bs
        order = np.argsort(self.slot_key, kind="stable")
        sk = self.slot_key[order]
        lo = np.searchsorted(sk, ekey, "left")
        cnt = np.searchsorted(sk, ekey, "right") - lo
        e_idx = np.repeat(np.arange(len(r)), cnt)
        first = np.repeat(lo - (np.cumsum(cnt) - cnt), cnt)
        s_idx = order[first + np.arange(len(e_idx))]
        vals = self.blocks[s_idx, r[e_idx] % bs, c[e_idx] % bs]
        got = np.bincount(e_idx, weights=vals, minlength=len(r))
        outside = int(np.count_nonzero(self.blocks)) \
            - int(np.count_nonzero(vals))
        return got, outside


class DenseView:
    """A dense output (the control's) read like :class:`TileView`."""

    def __init__(self, dense: np.ndarray):
        self.dense = dense

    def values_at(self, r: np.ndarray, c: np.ndarray):
        vals = self.dense[r, c].astype(np.float64)
        outside = int(np.count_nonzero(self.dense)) \
            - int(np.count_nonzero(vals))
        return vals, outside


def view(out) -> TileView:
    return TileView(out)


def reference(csr: sps.csr_matrix, inputs: dict) -> sps.coo_matrix:
    """``A @ A`` in float64 with scipy, as sorted coordinates."""
    c = (csr @ csr).tocoo()
    return c


def control(csr: sps.csr_matrix, inputs: dict) -> DenseView:
    """The reference put in the program's place, computed at ``high``
    precision (three bfloat16 passes) on the device."""
    return DenseView(check.high_dense_matmul(csr, csr))


def compare(got, ref: sps.coo_matrix) -> dict:
    """``max_err_ratio``: the largest ``|C - C_ref| / (|A| @ |A|)`` over
    the reference's entries (all weights are positive, so the divisor is
    ``C_ref`` itself); ``nonzeros_off_structure``: stored nonzeros where
    the reference has none, which has to be 0."""
    r = ref.row.astype(np.int64)
    c = ref.col.astype(np.int64)
    vals, outside = got.values_at(r, c)
    return {"max_err_ratio": check.max_err_ratio(vals, ref.data, ref.data),
            "nonzeros_off_structure": outside}


def work(csr: sps.csr_matrix, traffic: dict) -> dict:
    """The least work of ``A @ A`` in any format: ``2 sum_k nnz(A[:, k])
    nnz(A[k, :])`` flops (one multiply-add per matching pair of
    nonzeros); A's nonzeros read once and C's written once, value and
    index, 8 B each."""
    pattern = csr.astype(bool).astype(np.int8)
    mads = int(np.dot(np.diff(pattern.tocsc().indptr).astype(np.int64),
                      np.diff(pattern.indptr).astype(np.int64)))
    nnz_c = (pattern.astype(bool) @ pattern.astype(bool)).nnz
    return {"flops": 2.0 * mads, "bytes": 8.0 * (csr.nnz + nnz_c),
            "flops_peak": "bf16"}
