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
from .dist import tile_mesh
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
        blocks, rows, cols, nnzb, shape, logical = _host_bsr(
            dense, block_size, capacity, dtype)
        return cls(
            blocks=jnp.asarray(blocks),
            rows=jnp.asarray(rows),
            cols=jnp.asarray(cols),
            shape=shape,
            block_size=block_size,
            nnzb=nnzb,
            logical_shape=logical,
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


# Blocks gathered per numpy call: bounds the temporary of one gather.
_GATHER_CHUNK = 1024


def _out_dtype(src_dtype, dtype) -> np.dtype:
    """The stored blocks' dtype: ``dtype`` or the input's, as JAX keeps it
    (float64 is stored as float32 unless x64 is on)."""
    return np.dtype(jax.dtypes.canonicalize_dtype(dtype or src_dtype))


def _padded(dense: np.ndarray, shape: Tuple[int, int]) -> np.ndarray:
    """``dense`` zero-padded to ``shape``; the input itself when it already
    has that shape (no copy)."""
    if dense.shape == tuple(shape):
        return dense
    out = np.zeros(shape, dtype=dense.dtype)
    out[:dense.shape[0], :dense.shape[1]] = dense
    return out


def _block_mask(x: np.ndarray, bs: int) -> np.ndarray:
    """``bool[rows/bs, cols/bs]``: which ``bs x bs`` blocks of ``x`` hold a
    nonzero.  One block row at a time, so the temporaries are one block
    row's size, never the matrix's."""
    nbr, nbc = x.shape[0] // bs, x.shape[1] // bs
    mask = np.empty((nbr, nbc), dtype=bool)
    for r in range(nbr):
        slab = x[r * bs:(r + 1) * bs].reshape(bs, nbc, bs)
        mask[r] = (slab != 0).any(axis=(0, 2))
    return mask


def _gather_blocks(x: np.ndarray, bs: int, br: np.ndarray, bc: np.ndarray,
                   out: np.ndarray, at: np.ndarray) -> None:
    """``out[at[k]] = x``'s block ``(br[k], bc[k])``, converted to
    ``out``'s dtype, a chunk of blocks at a time."""
    x4 = x.reshape(x.shape[0] // bs, bs, x.shape[1] // bs, bs)
    for k in range(0, len(br), _GATHER_CHUNK):
        sl = slice(k, k + _GATHER_CHUNK)
        out[at[sl]] = x4[br[sl], :, bc[sl], :]


def _host_bsr(dense, block_size: int, capacity: Optional[int], dtype):
    """Host half of :meth:`BSR.from_dense`: the padded matrix's nonzero
    blocks in row-major order, padded to ``capacity`` with zero blocks
    that repeat the last (row, col).  Returns numpy ``(blocks, rows, cols,
    nnzb, shape, logical_shape)``."""
    dense = np.asarray(dense)
    m, n = dense.shape
    bs = block_size
    shape = (pad_to_multiple(m, bs), pad_to_multiple(n, bs))
    x = _padded(dense, shape)
    rr, cc = np.nonzero(_block_mask(x, bs))  # row-major: sorted by row
    nnzb = len(rr)
    # an all-zero matrix legitimately has capacity 0 (coverage blocks
    # added by the TiledBSR augmenter keep kernels well-defined)
    cap = capacity if capacity is not None else nnzb
    if nnzb > cap:
        raise ValueError(f"capacity {cap} < nnzb {nnzb}")
    blocks = np.zeros((cap, bs, bs), dtype=_out_dtype(dense.dtype, dtype))
    _gather_blocks(x, bs, rr, cc, blocks, np.arange(nnzb))
    rows, cols = _pad_last(rr, cap), _pad_last(cc, cap)
    return blocks, rows, cols, nnzb, shape, (m, n)


def _pad_last(idx: np.ndarray, cap: int) -> np.ndarray:
    """``int32[cap]``: ``idx`` then its last entry repeated (0 when empty),
    so a padded list stays sorted."""
    out = np.full((cap,), idx[-1] if len(idx) else 0, dtype=np.int32)
    out[:len(idx)] = idx
    return out


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
    block-row, merged in sorted order — see :meth:`_scan_dense`), so the
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

        Where the grid is ``g x g`` with ``g > 1`` and JAX has ``g * g``
        devices, tile (i, j) is uploaded straight to device (i, j) of
        :func:`repro.core.dist.make_grid_mesh`, the layout plans run in,
        so no device ever holds the whole matrix; otherwise the tiles go
        to the default device.
        """
        if balance not in ("none", "rows", "cols", "auto"):
            raise ValueError(f"unknown balance {balance!r}; one of "
                             "('none', 'rows', 'cols', 'auto')")
        with _obs.span("handle.tile.scan"):
            host = cls._scan_dense(dense, grid, block_size, capacity, dtype,
                                   balance)
        blocks, rows_, cols_, counts, fields = host
        mesh = tile_mesh(grid.rows) if grid.rows == grid.cols else None
        with _obs.span("handle.tile.upload"):
            if mesh is None:
                put = jnp.asarray
            else:
                tiles = jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec(*mesh.axis_names))

                def put(x):
                    return jax.device_put(x, tiles)
            # Block until the data is on the devices, traced or not, so the
            # handle comes back ready and the span ends when the upload
            # does (the puts may return while the copies are in flight).
            stored = jax.block_until_ready(
                (put(blocks), put(rows_), put(cols_), jnp.asarray(counts)))
        return cls(*stored, **fields)

    @staticmethod
    def _scan_dense(dense, grid: ProcessGrid, block_size: int, capacity,
                    dtype, balance: str) -> tuple:
        """Host half of :meth:`from_dense`, in numpy only: find the real
        blocks of every tile, gather them and merge the coverage blocks.
        The input is read in place (copied only where it needs padding);
        what it allocates is the stored arrays.  Returns those as numpy and
        the remaining fields."""
        dense = np.asarray(dense)
        m, n = dense.shape
        bs = block_size
        tm = pad_to_multiple(ceil_div(m, grid.rows), bs)
        tn = pad_to_multiple(ceil_div(n, grid.cols), bs)
        mp, np_ = tm * grid.rows, tn * grid.cols
        x = _padded(dense, (mp, np_))
        mask = _block_mask(x, bs)
        tile_nbr, tile_nbc = tm // bs, tn // bs

        def tile_counts(mk):
            return mk.reshape(grid.rows, tile_nbr, grid.cols,
                              tile_nbc).sum(axis=(1, 3))

        # Tiling reads block (row_src[r], col_src[c]) of the input where a
        # balance permutation puts block (r, c).
        row_src, col_src = np.arange(mask.shape[0]), np.arange(mask.shape[1])
        perm = col_perm = None
        if balance != "none":
            from .schedule import balance_row_perm

            # balance_row_perm equalizes grid-ROW (or grid-COL) totals; the
            # uniform capacity is the per-TILE max, which a permutation can
            # occasionally worsen (mass re-concentrating in one tile).
            # Keep an axis only when it strictly shrinks the capacity;
            # "auto" takes the axis with the larger shrink (rows on ties).
            best_cap = int(tile_counts(mask).max())
            best_axis = None
            if balance in ("rows", "auto"):
                p = np.asarray(balance_row_perm(mask.sum(axis=1), grid.rows))
                c = int(tile_counts(mask[p]).max())
                if c < best_cap:
                    best_axis, best_cap, row_src = "rows", c, p
            if balance in ("cols", "auto"):
                p = np.asarray(balance_row_perm(mask.sum(axis=0), grid.cols))
                c = int(tile_counts(mask[:, p]).max())
                if c < best_cap:
                    best_axis, best_cap, col_src = "cols", c, p
            if best_axis == "rows":
                col_src = np.arange(mask.shape[1])
                perm = tuple(int(p) for p in row_src)
            elif best_axis == "cols":
                row_src = np.arange(mask.shape[0])
                col_perm = tuple(int(p) for p in col_src)
            else:
                row_src = np.arange(mask.shape[0])
                col_src = np.arange(mask.shape[1])
        mask = mask[row_src][:, col_src]
        counts = tile_counts(mask).astype(np.int32)
        max_nnzb = int(counts.max())
        if capacity == "bucket":
            cap = bucket_capacity(max_nnzb)
        else:
            if capacity is not None and capacity < max_nnzb:
                raise ValueError(
                    f"capacity {capacity} < max tile nnzb {max_nnzb}")
            # an all-zero matrix keeps capacity 0: store_capacity is then
            # just the coverage blocks — the cheap empty fast path
            cap = capacity if capacity is not None else max_nnzb
        store = cap + tile_nbr
        g_shape = (grid.rows, grid.cols)
        blocks = np.zeros(g_shape + (store, bs, bs),
                          dtype=_out_dtype(dense.dtype, dtype))
        rows_ = np.empty(g_shape + (store,), dtype=np.int32)
        cols_ = np.empty(g_shape + (store,), dtype=np.int32)
        cov = np.arange(tile_nbr, dtype=np.int32)
        for i in range(grid.rows):
            for j in range(grid.cols):
                rr, cc = np.nonzero(mask[i * tile_nbr:(i + 1) * tile_nbr,
                                         j * tile_nbc:(j + 1) * tile_nbc])
                # The tile's list padded to the capacity (zero blocks that
                # repeat its last (row, col)), with one zero coverage block
                # per block row merged in by a stable sort: every block row
                # is visited, real blocks first and in row order.
                rows_aug = np.concatenate([_pad_last(rr, cap), cov])
                order = np.argsort(rows_aug, kind="stable")
                rows_[i, j] = rows_aug[order]
                cols_[i, j] = np.concatenate(
                    [_pad_last(cc, cap), np.zeros_like(cov)])[order]
                at = np.empty_like(order)
                at[order] = np.arange(store)
                _gather_blocks(x, bs, row_src[i * tile_nbr + rr],
                               col_src[j * tile_nbc + cc], blocks[i, j],
                               at[:len(rr)])
        return blocks, rows_, cols_, counts, dict(
            shape=(mp, np_), block_size=block_size,
            grid_shape=g_shape, capacity=cap,
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
