"""SpMM with a dense output, ``C = A @ B``: a graph's features aggregated
over its edges, as a GNN layer does.

How a product is built from the handles a user makes, its plain
reference, the lower-precision control, the comparison that decides
``correct``, and the product's format-independent work.

B is ``n x width`` float32 standard normals drawn from the run's seed (the
traffic mix gives ``width``), put on the grid with ``DistDense.for_rhs``.

Precision.  A is float32 with weights uniform on [0, 1), B float32, and
the configuration states float32 arithmetic (``matmul_precision``
``highest``, which the harness gives JAX before anything is traced).  The
program's SpMM kernel calls ``jnp.dot`` with no precision of its own, so
it multiplies at that precision: an error of some 2**-24 of ``|A| @ |B|``
per term summed.  The error is read against ``|A| @ |B|``, not against C,
since B's signs make entries of C cancel.  The control is the next
precision below, ``high``: three bfloat16 passes, which leave out what
lies beyond 16 significant bits of each operand, some 2**-17 of a
product.  The program's own default, one bfloat16 pass, errs by some
2**-9 and fails by far more.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sps

from harness import check

# Rows of A densified per call of the control (a dense scale-16 A is 17 GB).
_CONTROL_ROWS = 4096


def inputs(csr: sps.csr_matrix, traffic: dict, seed: int) -> dict:
    """B, ``n x width`` float32 standard normals from ``seed``, on the host
    (a stream apart from the one the generator draws A's weights from)."""
    rng = np.random.default_rng([seed, 1])
    return {"b": rng.standard_normal((csr.shape[1], traffic["width"]),
                                     dtype=np.float32)}


class Product:
    """One cell's product, built through the calls a user makes."""

    def __init__(self, a_h, inputs: dict, traffic: dict):
        from repro.core.api import DistDense

        self.traffic = traffic
        self.args = (a_h, DistDense.for_rhs(inputs["b"], a_h))
        self.plan = None

    def build_plan(self, mesh, impl: str) -> None:
        from repro.core.api import plan_matmul

        self.plan = plan_matmul(*self.args, impl=impl, mesh=mesh,
                                **self.traffic["plan"])

    def __call__(self):
        return self.plan(*self.args)

    @staticmethod
    def ready(out):
        """The array to block on: the dense output itself."""
        return out


def view(out) -> np.ndarray:
    """The program's output on the host."""
    return np.asarray(out)


def reference(csr: sps.csr_matrix, inputs: dict) -> dict:
    """``A @ B`` in float64 with scipy, and ``|A| @ |B|``, the scale each
    entry's error is read against."""
    b = inputs["b"]
    return {"c": csr @ b, "absprod": abs(csr) @ np.abs(b)}


def control(csr: sps.csr_matrix, inputs: dict) -> np.ndarray:
    """The reference put in the program's place, computed at ``high``
    precision (three bfloat16 passes) on the device, a panel of A's rows
    at a time."""
    b = inputs["b"]
    out = np.empty((csr.shape[0], b.shape[1]), np.float32)
    for r in range(0, csr.shape[0], _CONTROL_ROWS):
        rows = slice(r, r + _CONTROL_ROWS)
        out[rows] = check.high_dense_matmul(csr[rows], b)
    return out


def compare(got, ref: dict) -> dict:
    """``max_err_ratio``: the largest ``|C - C_ref| / (|A| @ |B|)`` over all
    entries (an entry whose ``|A| @ |B|`` is 0 must be 0)."""
    return {"max_err_ratio": check.max_err_ratio(got, ref["c"],
                                                 ref["absprod"])}


def work(csr: sps.csr_matrix, traffic: dict) -> dict:
    """The least work of ``A @ B`` in any format: one multiply-add per
    nonzero of A and column of B, ``2 nnz(A) width`` flops; A's nonzeros
    read once, value and index, 8 B each, B read once and C written once,
    4 B an entry."""
    n_b, n_c = csr.shape[1], csr.shape[0]
    width = traffic["width"]
    return {"flops": 2.0 * csr.nnz * width,
            "bytes": 8.0 * csr.nnz + 4.0 * width * (n_b + n_c),
            "flops_peak": "bf16"}
