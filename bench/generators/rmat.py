"""R-MAT graph generator, the benchmark's own copy.

Kept here, apart from the program's ``rmat_edges``, so that the inputs a
cell is measured on cannot change with the program.  The sampling is the
program's recursive quadrant sampling (Chakrabarti et al., "R-MAT", SDM
2004), vectorised over edges: every one of the ``scale`` bits of an edge's
row and column is drawn from the quadrant probabilities ``a, b, c, d``.

The edge list is fixed by the configuration (``graph_seed``): every run of
a cell multiplies the same structure, so runs with different ``--seed``
do the same work.  The run's seed draws the edge weights.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sps

WEIGHTS = "uniform[0,1) float32"


def edges(scale: int, edgefactor: int, a: float, b: float, c: float,
          d: float, seed: int) -> np.ndarray:
    """``int64[edgefactor << scale, 2]`` (row, col) pairs, duplicates kept."""
    rng = np.random.default_rng(seed)
    n_edges = edgefactor << scale
    probs = np.array([a, b, c, d], dtype=np.float64)
    probs = probs / probs.sum()
    rows = np.zeros(n_edges, dtype=np.int64)
    cols = np.zeros(n_edges, dtype=np.int64)
    for bit in range(scale):
        quad = rng.choice(4, size=n_edges, p=probs)
        rows |= ((quad >> 1) & 1).astype(np.int64) << bit
        cols |= (quad & 1).astype(np.int64) << bit
    return np.stack([rows, cols], axis=1)


def weighted_csr(config: dict, seed: int) -> sps.csr_matrix:
    """The configuration's graph as a float64 CSR adjacency.

    Duplicate edges are merged into one; each distinct edge gets a weight
    uniform on [0, 1) in float32 (so float64 holds it exactly), drawn from
    ``seed`` in the order of the sorted edge keys.
    """
    if config["weights"] != WEIGHTS:
        raise ValueError(f"weights {config['weights']!r}: this generator "
                         f"draws {WEIGHTS!r}")
    p = config["params"]
    scale = config["scale"]
    n = 1 << scale
    e = edges(scale, config["edgefactor"], p["a"], p["b"], p["c"], p["d"],
              config["graph_seed"])
    keys = np.unique(e[:, 0] * n + e[:, 1])
    w = np.random.default_rng(seed).random(len(keys), dtype=np.float32)
    csr = sps.csr_matrix((w.astype(np.float64), (keys // n, keys % n)),
                         shape=(n, n), dtype=np.float64)
    csr.sort_indices()
    return csr
