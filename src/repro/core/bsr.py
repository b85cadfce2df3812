"""Block-sparse (BSR) matrix pytrees and generators.

The paper stores each sparse tile as CSR (three RDMA-visible arrays:
values / rowptr / colind).  Scalar CSR wastes a TPU's MXU, so the TPU-native
data structure is *block* CSR: nonzeros are grouped into dense
``bs x bs`` blocks (bs = 128 in production, smaller in tests), and sparsity
lives at block granularity.  Blocks multiply on the MXU at full speed; the
block mask plays the role of the CSR structure arrays.

Two layouts:

* :class:`BSR` — one flat, statically-padded block list (sorted by block row)
  describing a single local matrix.  This is the layout the Pallas kernel
  consumes (scalar-prefetch of ``rows``/``cols`` drives the BlockSpec index
  maps).
* :class:`TiledBSR` — a ``grid.rows x grid.cols`` array of equally-padded BSR
  tiles for the distributed algorithms.  Uniform padding gives every device a
  static shape; the *padding itself* is the TPU manifestation of the paper's
  load imbalance (zero blocks still burn MXU cycles), which is exactly what
  the static rebalancing scheduler (``core/schedule.py``) shrinks.

Two tiling-time optimizations live here (see DESIGN.md "Sparsity-aware
capacity planning"):

* ``balance="rows"`` applies :func:`repro.core.schedule.balance_row_perm`
  to the global row blocks before tiling, spreading nonzero blocks evenly
  over grid rows so the uniform tile capacity (= executed MXU work per
  device) shrinks.  The permutation is carried on the result
  (``row_block_perm``) and inverted by the plan epilogue, so balanced and
  unbalanced plans produce identical outputs.
* TiledBSR tiles are stored *pre-augmented*: one zero block per block-row is
  merged (stably sorted) into each tile's block list at construction, so the
  SpMM kernel's coverage requirement (every output block-row visited) is met
  without any per-step concat + argsort inside the compiled ring loop.
  Stored per-tile length is therefore ``capacity + tile block-rows``
  (:attr:`TiledBSR.store_capacity`); ``capacity``/``counts`` keep counting
  *real* blocks only.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs as _obs
from .grid import ProcessGrid, bucket_capacity, ceil_div, pad_to_multiple

__all__ = ["BSR", "TiledBSR", "rmat_edges", "rmat_matrix", "random_sparse"]


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["blocks", "rows", "cols"],
    meta_fields=["shape", "block_size", "nnzb", "logical_shape"],
)
@dataclasses.dataclass
class BSR:
    """Flat padded block-sparse matrix.

    blocks : f[capacity, bs, bs]  — dense data per stored block (zeros pad)
    rows   : i32[capacity]        — block-row of each stored block, sorted
    cols   : i32[capacity]        — block-col of each stored block
    shape  : (m, n) logical shape (multiple of bs after construction padding)
    nnzb   : number of *valid* blocks (static Python int; <= capacity)

    Contract: blocks beyond the valid ones are ZERO (constructors guarantee
    it), so scatter-add consumers need no masking.  For a BSR built by
    :meth:`from_dense` the valid blocks are the prefix ``[:nnzb]``; a BSR
    extracted via :meth:`TiledBSR.tile` instead interleaves zero *coverage*
    blocks among the real ones (sorted merge), so there ``nnzb`` counts
    real blocks but is NOT a prefix length — do not slice ``[:nnzb]``.
    """

    blocks: jnp.ndarray
    rows: jnp.ndarray
    cols: jnp.ndarray
    shape: Tuple[int, int]
    block_size: int
    nnzb: int
    logical_shape: Optional[Tuple[int, int]] = None

    # ---------------------------------------------------------------- basics
    @property
    def capacity(self) -> int:
        return self.blocks.shape[0]

    @property
    def n_block_rows(self) -> int:
        return self.shape[0] // self.block_size

    @property
    def n_block_cols(self) -> int:
        return self.shape[1] // self.block_size

    @property
    def dtype(self):
        return self.blocks.dtype

    def block_fill_ratio(self) -> float:
        """Fraction of stored block entries that are nonzero (1.0 = perfect).

        Computed over blocks with any nonzero data (prefix-free, so it is
        also correct for the interleaved tiles of :meth:`TiledBSR.tile`);
        zero padding/coverage blocks never count against the ratio.
        """
        b = np.asarray(self.blocks)
        nz_blocks = int((np.abs(b).sum(axis=(1, 2)) != 0).sum())
        denom = max(nz_blocks, 1) * self.block_size**2
        return float(np.count_nonzero(b)) / float(denom)

    def flops(self, n_cols_dense: int) -> int:
        """MXU flops of BSR @ dense-with-n_cols (2*nnzb*bs^2*n)."""
        return 2 * self.nnzb * self.block_size**2 * n_cols_dense

    # ----------------------------------------------------------- conversions
    @classmethod
    def from_dense(
        cls,
        dense,
        block_size: int,
        capacity: Optional[int] = None,
        dtype=None,
    ) -> "BSR":
        dense = np.asarray(dense)
        m, n = dense.shape
        mp, np_ = pad_to_multiple(m, block_size), pad_to_multiple(n, block_size)
        padded = np.zeros((mp, np_), dtype=dense.dtype)
        padded[:m, :n] = dense
        nbr, nbc = mp // block_size, np_ // block_size
        view = padded.reshape(nbr, block_size, nbc, block_size).transpose(0, 2, 1, 3)
        mask = np.abs(view).sum(axis=(2, 3)) != 0
        rr, cc = np.nonzero(mask)  # np.nonzero returns row-major (sorted by row)
        nnzb = len(rr)
        # an all-zero matrix legitimately has capacity 0 (coverage blocks
        # added by the TiledBSR augmenter keep kernels well-defined)
        cap = capacity if capacity is not None else nnzb
        if nnzb > cap:
            raise ValueError(f"capacity {cap} < nnzb {nnzb}")
        bs = block_size
        blocks = np.zeros((cap, bs, bs), dtype=dense.dtype)
        rows = np.zeros((cap,), dtype=np.int32)
        cols = np.zeros((cap,), dtype=np.int32)
        blocks[:nnzb] = view[rr, cc]
        rows[:nnzb] = rr
        cols[:nnzb] = cc
        if nnzb > 0:  # keep padding sorted: repeat the last (row, col)
            rows[nnzb:] = rr[-1]
            cols[nnzb:] = cc[-1]
        out_dtype = dtype or dense.dtype
        return cls(
            blocks=jnp.asarray(blocks, dtype=out_dtype),
            rows=jnp.asarray(rows),
            cols=jnp.asarray(cols),
            shape=(mp, np_),
            block_size=bs,
            nnzb=nnzb,
            logical_shape=(m, n),
        )

    @classmethod
    def from_scipy(cls, sp_mat, block_size: int, capacity: Optional[int] = None,
                   dtype=None) -> "BSR":
        import scipy.sparse as sps

        sp_mat = sps.csr_matrix(sp_mat)
        return cls.from_dense(sp_mat.toarray(), block_size, capacity, dtype)

    def to_dense(self) -> jnp.ndarray:
        # Padding / coverage blocks are zero by construction (from_dense,
        # with_capacity and the TiledBSR augmenter all guarantee it), so a
        # plain scatter-add is exact even when valid blocks are interleaved
        # with zero coverage blocks (the pre-augmented tile layout).
        bs = self.block_size
        nbr, nbc = self.n_block_rows, self.n_block_cols
        out = jnp.zeros((nbr, nbc, bs, bs), dtype=self.dtype)
        out = out.at[self.rows, self.cols].add(self.blocks)
        return out.transpose(0, 2, 1, 3).reshape(nbr * bs, nbc * bs)

    def with_capacity(self, capacity: int) -> "BSR":
        """Re-pad to a new (>= current) capacity — used to unify tile shapes.

        Shrinking is refused: valid blocks are not necessarily a prefix
        (see the class contract), so truncation could silently drop data.
        """
        pad = capacity - self.capacity
        if pad == 0:
            return self
        if pad < 0:
            raise ValueError(
                f"cannot shrink capacity {self.capacity} -> {capacity}: "
                "stored blocks are not necessarily a prefix; rebuild with "
                "from_dense(capacity=...) instead")
        last_r = self.rows[-1] if self.capacity else jnp.zeros((), jnp.int32)
        last_c = self.cols[-1] if self.capacity else jnp.zeros((), jnp.int32)
        blocks = jnp.concatenate(
            [self.blocks,
             jnp.zeros((pad, self.block_size, self.block_size), self.dtype)])
        rows = jnp.concatenate([self.rows, jnp.full((pad,), last_r, jnp.int32)])
        cols = jnp.concatenate([self.cols, jnp.full((pad,), last_c, jnp.int32)])
        return BSR(blocks, rows, cols, self.shape, self.block_size, self.nnzb,
                   self.logical_shape)


def _augment_tile(blocks: np.ndarray, rows: np.ndarray, cols: np.ndarray,
                  n_block_rows: int):
    """Merge one zero block per block-row into a tile's block list (sorted).

    This is the SpMM kernel's coverage requirement — every output block-row
    must be visited so the first-visit zeroing initializes the whole C tile —
    precomputed at tiling time instead of per ring step.  The stable sort
    keeps real blocks in row order and the appended zero blocks inert.
    """
    cov = np.arange(n_block_rows, dtype=rows.dtype)
    rows_aug = np.concatenate([rows, cov])
    order = np.argsort(rows_aug, kind="stable")
    bs = blocks.shape[1]
    blocks_aug = np.concatenate(
        [blocks, np.zeros((n_block_rows, bs, bs), blocks.dtype)])[order]
    cols_aug = np.concatenate(
        [cols, np.zeros((n_block_rows,), cols.dtype)])[order]
    return blocks_aug, rows_aug[order], cols_aug


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["blocks", "rows", "cols", "counts"],
    meta_fields=["shape", "block_size", "grid_shape", "capacity",
                 "logical_shape", "row_block_perm", "col_block_perm"],
)
@dataclasses.dataclass
class TiledBSR:
    """A grid of uniformly-padded, coverage-augmented BSR tiles.

    blocks : f[gr, gc, store_cap, bs, bs]  (store_cap = capacity + tile nbr)
    rows   : i32[gr, gc, store_cap]  block-row *within the tile*, sorted;
                                     every block-row present at least once
    cols   : i32[gr, gc, store_cap]  block-col *within the tile*
    counts : i32[gr, gc]    *real* blocks per tile (the load-imbalance map)

    Stored arrays are pre-augmented for kernel coverage (zero block per
    block-row, merged in sorted order — see :func:`_augment_tile`), so the
    distributed hot loop consumes them as-is.  ``capacity`` counts real
    block slots only; zero padding/coverage blocks are inert under the
    scatter-add consumers (``to_dense``, ``densify_raw``, the ref SpMM).

    ``row_block_perm`` (optional) records a load-balancing permutation of
    the *global* row blocks applied before tiling (``balance="rows"``):
    position ``t`` holds original row block ``row_block_perm[t]``.  The plan
    epilogue inverts it on the output, so results match unbalanced plans.
    ``col_block_perm`` is the column-axis analogue (``balance="cols"``):
    position ``t`` holds original column block ``col_block_perm[t]``.  A
    column permutation of the *left* operand permutes the contraction
    dimension, so the planner compensates by permuting the right operand's
    row blocks before the multiply; on the *right* operand the output's
    column blocks inherit the permutation and the epilogue inverts it.
    """

    blocks: jnp.ndarray
    rows: jnp.ndarray
    cols: jnp.ndarray
    counts: jnp.ndarray
    shape: Tuple[int, int]      # padded global shape
    block_size: int
    grid_shape: Tuple[int, int]
    capacity: int
    logical_shape: Optional[Tuple[int, int]] = None
    row_block_perm: Optional[Tuple[int, ...]] = None
    col_block_perm: Optional[Tuple[int, ...]] = None

    @property
    def tile_shape(self) -> Tuple[int, int]:
        return (self.shape[0] // self.grid_shape[0],
                self.shape[1] // self.grid_shape[1])

    @property
    def store_capacity(self) -> int:
        """Stored block slots per tile: capacity + coverage augmentation."""
        return self.blocks.shape[2]

    @property
    def dtype(self):
        return self.blocks.dtype

    @classmethod
    def from_dense(cls, dense, grid: ProcessGrid, block_size: int,
                   capacity=None, dtype=None,
                   balance: str = "none") -> "TiledBSR":
        """Tile a dense array into uniformly-padded BSR tiles.

        ``capacity`` is the uniform real-block capacity: an int pins it,
        ``None`` derives the minimum (max tile nnzb), and ``"bucket"``
        derives the minimum and rounds it up to the next 1.25x bucket
        (:func:`repro.core.grid.bucket_capacity`) so near-identical
        sparsity patterns share abstract shapes — and therefore cached,
        jitted plans.

        ``balance`` permutes global row blocks (``"rows"``), column blocks
        (``"cols"``) or whichever axis shrinks the capacity most
        (``"auto"``) before tiling; the permutation is carried as
        ``row_block_perm`` / ``col_block_perm`` and undone by the planner.
        An axis is only kept when it *strictly* shrinks the capacity.
        """
        if balance not in ("none", "rows", "cols", "auto"):
            raise ValueError(f"unknown balance {balance!r}; one of "
                             "('none', 'rows', 'cols', 'auto')")
        with _obs.span("handle.tile.scan"):
            host = cls._scan_dense(dense, grid, block_size, capacity, dtype,
                                   balance)
        blocks, rows_, cols_, counts, fields = host
        with _obs.span("handle.tile.upload"):
            # Block until the data is on the device, traced or not, so the
            # handle comes back ready and the span ends when the upload
            # does (jnp.asarray may return while the copy is in flight).
            stored = jax.block_until_ready(tuple(
                jnp.asarray(x) for x in (blocks, rows_, cols_, counts)))
        return cls(*stored, **fields)

    @staticmethod
    def _scan_dense(dense, grid: ProcessGrid, block_size: int, capacity,
                    dtype, balance: str) -> tuple:
        """Host half of :meth:`from_dense`: pad, find the real blocks of
        every tile, gather them and merge the coverage blocks.  Returns the
        stored arrays as numpy and the remaining fields."""
        dense = np.asarray(dense)
        m, n = dense.shape
        tm = pad_to_multiple(ceil_div(m, grid.rows), block_size)
        tn = pad_to_multiple(ceil_div(n, grid.cols), block_size)
        mp, np_ = tm * grid.rows, tn * grid.cols
        padded = np.zeros((mp, np_), dtype=dense.dtype)
        padded[:m, :n] = dense
        perm = col_perm = None
        if balance != "none":
            from .schedule import balance_row_perm
            nbr_global = mp // block_size
            nbc_global = np_ // block_size
            mask = np.abs(
                padded.reshape(nbr_global, block_size, nbc_global,
                               block_size)).sum(axis=(1, 3)) != 0

            def tile_cap(m):
                per_tile = m.reshape(grid.rows, nbr_global // grid.rows,
                                     grid.cols, nbc_global // grid.cols)
                return int(per_tile.sum(axis=(1, 3)).max())

            # balance_row_perm equalizes grid-ROW (or grid-COL) totals; the
            # uniform capacity is the per-TILE max, which a permutation can
            # occasionally worsen (mass re-concentrating in one tile).
            # Keep an axis only when it strictly shrinks the capacity;
            # "auto" takes the axis with the larger shrink (rows on ties).
            best_cap = tile_cap(mask)
            best_axis = None
            if balance in ("rows", "auto"):
                p = balance_row_perm(mask.sum(axis=1), grid.rows)
                c = tile_cap(mask[np.asarray(p)])
                if c < best_cap:
                    best_axis, best_cap, perm = "rows", c, p
            if balance in ("cols", "auto"):
                p = balance_row_perm(mask.sum(axis=0), grid.cols)
                c = tile_cap(mask[:, np.asarray(p)])
                if c < best_cap:
                    best_axis, best_cap, col_perm = "cols", c, p
            if best_axis == "rows":
                col_perm = None
                padded = padded.reshape(nbr_global, block_size, np_)[perm]
                padded = padded.reshape(mp, np_)
                perm = tuple(int(p) for p in perm)
            elif best_axis == "cols":
                perm = None
                padded = padded.reshape(mp, nbc_global,
                                        block_size)[:, col_perm]
                padded = padded.reshape(mp, np_)
                col_perm = tuple(int(p) for p in col_perm)
            else:
                perm = col_perm = None
        tiles = []
        for i in range(grid.rows):
            row = []
            for j in range(grid.cols):
                row.append(BSR.from_dense(
                    padded[i * tm:(i + 1) * tm, j * tn:(j + 1) * tn],
                    block_size, dtype=dtype))
            tiles.append(row)
        max_nnzb = max(max(t.nnzb for t in row) for row in tiles)
        if capacity == "bucket":
            cap = bucket_capacity(max_nnzb)
        else:
            if capacity is not None and capacity < max_nnzb:
                raise ValueError(
                    f"capacity {capacity} < max tile nnzb {max_nnzb}")
            # an all-zero matrix keeps capacity 0: store_capacity is then
            # just the coverage blocks — the cheap empty fast path
            cap = capacity if capacity is not None else max_nnzb
        tile_nbr = tm // block_size
        aug = [[_augment_tile(np.asarray(t.blocks), np.asarray(t.rows),
                              np.asarray(t.cols), tile_nbr)
                for t in (u.with_capacity(cap) for u in row)]
               for row in tiles]
        blocks = np.stack([np.stack([a[0] for a in row]) for row in aug])
        rows_ = np.stack([np.stack([a[1] for a in row]) for row in aug])
        cols_ = np.stack([np.stack([a[2] for a in row]) for row in aug])
        counts = np.asarray([[t.nnzb for t in row] for row in tiles],
                            dtype=np.int32)
        return blocks, rows_, cols_, counts, dict(
            shape=(mp, np_), block_size=block_size,
            grid_shape=(grid.rows, grid.cols), capacity=cap,
            logical_shape=(m, n), row_block_perm=perm,
            col_block_perm=col_perm)

    def to_dense(self) -> jnp.ndarray:
        gr, gc = self.grid_shape
        tm, tn = self.tile_shape
        out = np.zeros(self.shape, dtype=self.blocks.dtype)
        for i in range(gr):
            for j in range(gc):
                t = BSR(self.blocks[i, j], self.rows[i, j], self.cols[i, j],
                        (tm, tn), self.block_size, int(self.counts[i, j]))
                out[i * tm:(i + 1) * tm, j * tn:(j + 1) * tn] = np.asarray(
                    t.to_dense())
        return jnp.asarray(out)

    def tile(self, i: int, j: int) -> BSR:
        """View tile (i, j) as a flat BSR.

        The returned BSR shares the stored *pre-augmented* arrays: zero
        coverage blocks are interleaved with the real ones, so its ``nnzb``
        counts real blocks but is not a prefix length (safe for zero-inert
        consumers like ``to_dense``/``flops``; do not slice ``[:nnzb]``).
        """
        return BSR(self.blocks[i, j], self.rows[i, j], self.cols[i, j],
                   self.tile_shape, self.block_size, int(self.counts[i, j]))

    # ------------------------------------------------------ imbalance metrics
    def load_imbalance(self) -> float:
        """max/avg valid-block count over tiles — the paper's Table 1 metric."""
        c = np.asarray(self.counts, dtype=np.float64)
        avg = c.mean()
        return float(c.max() / avg) if avg > 0 else 1.0

    def padded_flop_waste(self) -> float:
        """Fraction of MXU block-matmuls that operate on padding.

        Uniform static padding means every device executes ``capacity`` block
        products per tile; only ``counts`` of them are real.  This is the
        paper's per-stage load imbalance made physical on a TPU.
        """
        c = np.asarray(self.counts, dtype=np.float64)
        total = self.capacity * c.size
        return float(1.0 - c.sum() / total) if total else 0.0


# --------------------------------------------------------------------------
# Generators
# --------------------------------------------------------------------------
def rmat_edges(scale: int, edgefactor: int = 8,
               a: float = 0.6, b: float = 0.4 / 3, c: float = 0.4 / 3,
               d: float = 0.4 / 3, seed: int = 0) -> np.ndarray:
    """R-MAT edge list (paper Fig. 1 uses a=0.6, b=c=d=0.4/3, ef=8, scale 17).

    Returns int64[nedges, 2].  Vectorized recursive bit sampling.
    """
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


def rmat_matrix(scale: int, edgefactor: int = 8, seed: int = 0,
                dtype=np.float32, **kw):
    """Dense numpy adjacency matrix from R-MAT edges (small scales only)."""
    n = 1 << scale
    e = rmat_edges(scale, edgefactor, seed=seed, **kw)
    m = np.zeros((n, n), dtype=dtype)
    m[e[:, 0], e[:, 1]] = 1.0
    return m


def random_sparse(m: int, n: int, density: float, seed: int = 0,
                  dtype=np.float32) -> np.ndarray:
    """Uniform random sparse dense-array (for tests/benchmarks)."""
    rng = np.random.default_rng(seed)
    mat = rng.standard_normal((m, n)).astype(dtype)
    mask = rng.random((m, n)) < density
    return mat * mask
