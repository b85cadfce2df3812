"""Element codec: a sparse tile on the wire as its nonzero entries.

A stored BSR tile is ``[slots, bs, bs]`` dense blocks, and on a power-law
graph almost all of it is zero: the scale-16 R-MAT tiles hold 4 to 9
nonzeros per 128 x 128 block.  Shipped as blocks, a ring shift moves the
zeros too.  The codec ships a tile as two flat arrays instead:

* ``vals[cap]`` — the tile's nonzero entries, in row-major order of the
  stored blocks (slot, then row, then column);
* ``idx[cap]`` — each entry's flat position ``slot * bs * bs + r * bs +
  c`` in the ``[slots, bs, bs]`` tile, ascending.

``cap`` is one static length for every tile of an operand (the bucketed
largest per-tile count, :func:`entry_capacity`), so a shard_map body sees
one shape.  A tile with fewer entries pads with ``vals`` 0 at positions
past the tile's end, distinct and ascending, which the decode drops.

An entry is selected by its bit pattern, not by its value, so ``-0.0``,
NaN and inf ride like any other value and the decoded tile equals the
stored one bit for bit: only the positions that hold ``+0.0`` are left
to the decode's zero fill.

Encoding runs once per handle, on each tile's own device
(:func:`encode`); decoding runs on the receiver after every shift, one
XLA scatter (:func:`repro.kernels.ref.entry_decode_ref`).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from .grid import bucket_capacity

__all__ = ["MAX_BLOCK", "ENCODE_CHUNK", "entry_capacity", "entries_win",
           "count", "encode", "entry_bytes"]

# Flat positions are int32: a tile may hold at most this many entries'
# worth of positions, padding included.
_INDEX_LIMIT = 2 ** 31
# The largest block size whose running counts the encoder's bfloat16
# prefix sums hold exactly (:func:`_prefix`).
MAX_BLOCK = 256
# Entries the encoder places at a time: its work space is a few
# ``[ENCODE_CHUNK, bs]`` arrays (about 25 MB at bs 128), whatever the
# tile's count.
ENCODE_CHUNK = 16384

# One v5e's costs, measured on the 2x2 SpMM cell's tile (PERF.md section
# 6): a ring shift moves A's blocks at about 47 GB/s; XLA's decode
# fills a 2.604 GB tile with zeros in 4.66 ms (559 GB/s) and scatters
# 287,109 entries in 1.72 ms more (6.0 ns an entry).
_SHIFT_BYTES_S = 47e9
_FILL_BYTES_S = 559e9
_DECODE_ENTRY_S = 6.0e-9
# The share of a block shift that the local matmul leaves exposed (62% in
# the 2x2 cell: 34.7 of about 56 ms); the decode runs after the shift's
# done op, exposed in full.
_EXPOSED_SHARE = 0.5


def entry_capacity(max_count: int) -> int:
    """The static entry length for an operand whose fullest tile holds
    ``max_count`` nonzeros: bucketed like block capacities (1.25x steps),
    so handles of near-equal density share plan shapes; at least 1."""
    return max(bucket_capacity(int(max_count)), 1)


def entry_bytes(cap: int, itemsize: int) -> int:
    """Bytes of one encoded tile's entries: a value and an int32 position
    each."""
    return cap * (itemsize + 4)


def entries_win(cap: int, store: int, block_size: int, itemsize: int
                ) -> bool:
    """Whether a tile of ``store`` slots ships as ``cap`` entries: when
    shipping and decoding them takes less time than the exposed part of a
    shift of the tile's blocks, by the costs measured on a v5e (so below
    about 0.6% of a float32 tile's positions), the block size is at most
    :data:`MAX_BLOCK` and every position, padding included, fits int32."""
    slot_elems = store * block_size * block_size
    tile = slot_elems * itemsize
    entries_s = (entry_bytes(cap, itemsize) / _SHIFT_BYTES_S
                 + tile / _FILL_BYTES_S + cap * _DECODE_ENTRY_S)
    return (block_size <= MAX_BLOCK and slot_elems + cap < _INDEX_LIMIT
            and entries_s < _EXPOSED_SHARE * tile / _SHIFT_BYTES_S)


def _bits(x):
    """``x`` as unsigned integers of its width: nonzero exactly where an
    entry's bit pattern is (so ``-0.0`` and NaN count, ``+0.0`` not)."""
    return jax.lax.bitcast_convert_type(
        x, jnp.dtype(f"uint{8 * x.dtype.itemsize}"))


def _count_tile(blocks):
    return (_bits(blocks) != 0).sum(dtype=jnp.int32)


def _prefix(x):
    """Inclusive prefix sums along the last axis of a non-negative int32
    ``x`` whose entries are at most 256: one matmul with a triangular matrix
    of ones, exact (bfloat16 holds each entry, the float32 accumulator
    each sum below 2**24).  Compiles in a fraction of the time a long
    ``cumsum`` takes on the TPU."""
    n = x.shape[-1]
    tri = jnp.triu(jnp.ones((n, n), jnp.bfloat16))
    return lax.dot_general(
        x.astype(jnp.bfloat16), tri, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(jnp.int32)


def _encode_tile(blocks, cap: int):
    """One ``[slots, bs, bs]`` tile's entries at length ``cap`` (which
    must hold the tile's count), placed :data:`ENCODE_CHUNK` at a time.  Gathers
    only, no compaction by scatter: entry e lies in the slot whose running
    count first passes e, in the row of that slot whose running count
    first passes e's rank in the slot, and is that row's k-th nonzero for
    its rank k in the row."""
    slots, bs, _ = blocks.shape
    if bs > MAX_BLOCK:
        raise ValueError(f"block size {bs} above {MAX_BLOCK}: the running "
                         "counts would round")
    per_row = (_bits(blocks) != 0).sum(axis=2, dtype=jnp.int32)  # [S, bs]
    in_slot = _prefix(per_row)                       # running, per slot
    per_slot = in_slot[:, -1]
    # running count over the slots, bs slots a matmul: each slot's count
    # (at most bs * bs) as its high and low bytes
    n_groups = -(-slots // bs)
    grouped = jnp.pad(per_slot, (0, n_groups * bs - slots)).reshape(
        n_groups, bs)
    in_group = (_prefix(grouped >> 8) << 8) + _prefix(grouped & 255)
    before_group = jnp.cumsum(in_group[:, -1]) - in_group[:, -1]
    slot_end = (in_group + before_group[:, None]).reshape(-1)[:slots]
    total = slot_end[-1]

    def place(e):
        s = jnp.minimum(jnp.searchsorted(slot_end, e, side="right"),
                        slots - 1).astype(jnp.int32)
        k = e - (slot_end[s] - per_slot[s])          # rank in the slot
        run = in_slot[s]                             # [chunk, bs]
        r = jnp.minimum((run <= k[:, None]).sum(axis=1, dtype=jnp.int32),
                        bs - 1)
        k = k - (jnp.take_along_axis(run, r[:, None], axis=1)[:, 0]
                 - per_row[s, r])                    # rank in the row
        row = blocks[s, r]                           # [chunk, bs]
        before = _prefix((_bits(row) != 0).astype(jnp.int32))
        col = jnp.minimum(
            (before <= k[:, None]).sum(axis=1, dtype=jnp.int32), bs - 1)
        val = jnp.take_along_axis(row, col[:, None], axis=1)[:, 0]
        real = e < total
        idx = jnp.where(real, (s * bs + r) * bs + col,
                        slots * bs * bs + e - total)
        return jnp.where(real, val, jnp.zeros_like(val)), idx

    chunk = min(ENCODE_CHUNK, cap)
    n = -(-cap // chunk)
    vals, idx = lax.map(place, jnp.arange(n * chunk, dtype=jnp.int32)
                        .reshape(n, chunk))
    return vals.reshape(-1)[:cap], idx.reshape(-1)[:cap]


def _tilewise(fn, mesh, blocks, out_tree):
    """``fn`` over every tile of a ``[g, g, ...]`` array: inside a
    shard_map on ``mesh`` (tile (i, j) on device (i, j)), or batched on
    the array's one device where ``mesh`` is None.  ``out_tree`` has the
    structure of ``fn``'s result."""
    if mesh is None:
        return jax.vmap(jax.vmap(fn))(blocks)
    spec = P(*mesh.axis_names)

    def local(t):
        return jax.tree.map(lambda x: x[None, None], fn(t[0, 0]))

    return jax.shard_map(local, mesh=mesh, in_specs=spec,
                         out_specs=jax.tree.map(lambda _: spec, out_tree),
                         check_vma=False)(blocks)


@functools.partial(jax.jit, static_argnums=1)
def _count(blocks, mesh):
    return _tilewise(_count_tile, mesh, blocks, 0)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _encode(blocks, mesh, cap: int):
    vals, idx = _tilewise(functools.partial(_encode_tile, cap=cap), mesh,
                          blocks, (0, 0))
    return {"vals": vals, "idx": idx}


def count(blocks, mesh: Optional[jax.sharding.Mesh]) -> np.ndarray:
    """Nonzero entries of each tile of ``blocks`` (``[g, g, slots, bs,
    bs]``), as host ``int64[g, g]``; ``mesh`` as for :func:`encode`."""
    return np.asarray(_count(blocks, mesh), dtype=np.int64)


def encode(blocks, mesh: Optional[jax.sharding.Mesh], cap: int
           ) -> Dict[str, jnp.ndarray]:
    """Every tile of ``blocks`` (``[g, g, slots, bs, bs]``) as its entries:
    ``{"vals": [g, g, cap], "idx": int32[g, g, cap]}``.  With ``mesh``
    (``blocks`` laid out one tile per device of it) each tile is encoded
    on its own device and the result is laid out the same way; without,
    where ``blocks`` lives.  ``cap`` must hold every tile's count."""
    return _encode(blocks, mesh, int(cap))
