"""Tile placement (skew layouts) and mesh helpers for the distributed
algorithms.

The paper's iteration offset ``k_offset = i + j`` (SS3.3) balances
communication and makes the first fetch local.  On a torus we realize the
offset at *tile-placement time*: the distributed matrix constructor places
tile ``A[i, (i+j) % g]`` at mesh position (i, j) ("skew_rows"), which costs
nothing at runtime — it is the TPU analogue of remapping the paper's global
pointer directory.  The ring algorithms then only ever talk to nearest
neighbours.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .bsr import TiledBSR

__all__ = [
    "make_grid_mesh", "tileize", "untileize",
    "skew_dense", "skew_bsr", "place_b_for_stationary_a", "unskew_c_rows",
]


def make_grid_mesh(g: int, axis_row: str = "row", axis_col: str = "col"):
    """A g x g device mesh with Auto axis types."""
    return jax.make_mesh((g, g), (axis_row, axis_col),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def tileize(x: jnp.ndarray, g: int) -> jnp.ndarray:
    """[M, N] -> [g, g, M/g, N/g] tile grid view."""
    m, n = x.shape
    return x.reshape(g, m // g, g, n // g).transpose(0, 2, 1, 3)


def untileize(t: jnp.ndarray) -> jnp.ndarray:
    g1, g2, tm, tn = t.shape
    return t.transpose(0, 2, 1, 3).reshape(g1 * tm, g2 * tn)


def _roll_rows(tiles: jnp.ndarray, sign: int) -> jnp.ndarray:
    """tiles[i, j] <- tiles[i, (j + sign*i) % g]  (row-dependent column roll)."""
    g = tiles.shape[0]
    i = np.arange(g)[:, None]
    j = np.arange(g)[None, :]
    src = (j + sign * i) % g
    return tiles[i, src]


def _roll_cols(tiles: jnp.ndarray, sign: int) -> jnp.ndarray:
    """tiles[i, j] <- tiles[(i + sign*j) % g, j]  (col-dependent row roll)."""
    g = tiles.shape[0]
    i = np.arange(g)[:, None]
    j = np.arange(g)[None, :]
    src = (i + sign * j) % g
    return tiles[src, j]


def skew_dense(x: jnp.ndarray, g: int, kind: str) -> jnp.ndarray:
    """Skew a global dense matrix's tile grid.

    kind='rows': position (i,j) holds tile (i, (i+j)%g)   [A operand]
    kind='cols': position (i,j) holds tile ((i+j)%g, j)   [B operand]
    """
    tiles = tileize(x, g)
    if kind == "rows":
        tiles = _roll_rows(tiles, +1)
    elif kind == "cols":
        tiles = _roll_cols(tiles, +1)
    else:
        raise ValueError(kind)
    return untileize(tiles)


def skew_bsr(a: TiledBSR, kind: str) -> TiledBSR:
    """Skew a TiledBSR's tile grid (same placement semantics as skew_dense)."""
    g = a.grid_shape[0]
    if a.grid_shape[0] != a.grid_shape[1]:
        raise ValueError("skew needs a square grid")
    i = np.arange(g)[:, None]
    j = np.arange(g)[None, :]
    if kind == "rows":
        si, sj = i + 0 * j, (j + i) % g
    elif kind == "cols":
        si, sj = (i + j) % g, j + 0 * i
    else:
        raise ValueError(kind)
    take = lambda arr: arr[si, sj]
    return TiledBSR(
        blocks=take(a.blocks), rows=take(a.rows), cols=take(a.cols),
        counts=take(a.counts), shape=a.shape, block_size=a.block_size,
        grid_shape=a.grid_shape, capacity=a.capacity,
        logical_shape=a.logical_shape, row_block_perm=a.row_block_perm,
        col_block_perm=a.col_block_perm)


def place_b_for_stationary_a(b: jnp.ndarray, g: int) -> jnp.ndarray:
    """Initial B placement for the stationary-A ring.

    Mesh position (i, k) holds B tile (k, (i+k) % g): the owner of A[i, k]
    starts with the B tile for its first output column j0 = (i+k) % g — the
    paper's ``k_offset = i + k`` for stationary A.
    """
    tiles = tileize(b, g)
    i = np.arange(g)[:, None]
    k = np.arange(g)[None, :]
    return untileize(tiles[k + 0 * i, (i + k) % g])


def unskew_c_rows(c: jnp.ndarray, g: int) -> jnp.ndarray:
    """Invert 'rows' skew on the output: position (i,j) held tile (i,(i+j)%g)."""
    tiles = tileize(c, g)
    return untileize(_roll_rows(tiles, -1))
