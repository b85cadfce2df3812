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

__all__ = [
    "make_grid_mesh", "tile_mesh", "tileize", "untileize",
    "skew_dense", "unskew_c_rows",
]


def make_grid_mesh(g: int, axis_row: str = "row", axis_col: str = "col"):
    """A g x g device mesh with Auto axis types."""
    return jax.make_mesh((g, g), (axis_row, axis_col),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def tile_mesh(g: int):
    """The mesh a ``g x g`` grid of tiles is put on when it is made: tile
    (i, j) on device (i, j) of :func:`make_grid_mesh`, the layout plans
    run in.  ``None`` (the default device) for ``g == 1`` or fewer than
    ``g * g`` devices."""
    if g > 1 and len(jax.devices()) >= g * g:
        return make_grid_mesh(g)
    return None


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


def unskew_c_rows(c: jnp.ndarray, g: int) -> jnp.ndarray:
    """Invert 'rows' skew on the output: position (i,j) held tile (i,(i+j)%g)."""
    tiles = tileize(c, g)
    return untileize(_roll_rows(tiles, -1))
