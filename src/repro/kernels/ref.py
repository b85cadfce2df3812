"""Pure-jnp oracles for the Pallas kernels.

These are the correctness references (``assert_allclose`` targets) and also
the portable fallback used inside ``shard_map`` on CPU test meshes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "bsr_spmm_ref",
    "bsr_spmm_raw_ref",
    "bsr_pair_matmul_raw_ref",
    "bsr_pair_accumulate_raw_ref",
    "densify_raw",
    "entry_decode_ref",
]


def bsr_spmm_raw_ref(blocks, rows, cols, dense, n_block_rows: int,
                     out_dtype=None):
    """C = BSR(blocks, rows, cols) @ dense.

    blocks : f[cap, bs, bs]  (padding blocks are zero)
    rows   : i32[cap] block-row per stored block
    cols   : i32[cap] block-col per stored block
    dense  : f[n_block_cols*bs, n]
    returns f[n_block_rows*bs, n]
    """
    cap, bs, _ = blocks.shape
    n = dense.shape[1]
    out_dtype = out_dtype or jnp.promote_types(blocks.dtype, dense.dtype)
    b_blocks = dense.reshape(-1, bs, n)[cols]                      # [cap, bs, n]
    partial = jnp.einsum(
        "kab,kbn->kan", blocks, b_blocks,
        preferred_element_type=jnp.float32)                        # [cap, bs, n]
    out = jnp.zeros((n_block_rows, bs, n), dtype=jnp.float32)
    out = out.at[rows].add(partial)
    return out.reshape(n_block_rows * bs, n).astype(out_dtype)


def bsr_spmm_ref(a_bsr, dense):
    """Oracle via explicit densification: to_dense(A) @ B."""
    acc = jnp.dot(a_bsr.to_dense().astype(jnp.float32),
                  dense.astype(jnp.float32))
    return acc.astype(jnp.promote_types(a_bsr.dtype, dense.dtype))


def bsr_pair_matmul_raw_ref(a_blocks, b_blocks, pair_a, pair_b, pair_rows,
                            pair_cols, n_block_rows: int, n_block_cols: int,
                            out_dtype=None):
    """Sparse x sparse block-pair products, accumulated into a dense tile.

    For host-known sparsity structure: ``pair_a[k]``/``pair_b[k]`` index the
    stored blocks of A and B whose product contributes to output block
    ``(pair_rows[k], pair_cols[k])``.  Padding pairs must point at zero blocks.
    """
    bs = a_blocks.shape[1]
    prods = jnp.einsum(
        "kab,kbc->kac", a_blocks[pair_a], b_blocks[pair_b],
        preferred_element_type=jnp.float32)                        # [P, bs, bs]
    out = jnp.zeros((n_block_rows, n_block_cols, bs, bs), jnp.float32)
    out = out.at[pair_rows, pair_cols].add(prods)
    out = out.transpose(0, 2, 1, 3).reshape(n_block_rows * bs, n_block_cols * bs)
    out_dtype = out_dtype or jnp.promote_types(a_blocks.dtype, b_blocks.dtype)
    return out.astype(out_dtype)


def bsr_pair_accumulate_raw_ref(a_blocks, b_blocks, pair_a, pair_b,
                                pair_slot, n_slots: int):
    """Sparse x sparse block-pair products, accumulated into PACKED blocks.

    The sparse-output sibling of :func:`bsr_pair_matmul_raw_ref`: instead
    of scattering into a dense ``(nbr, nbc)`` block grid, products land in
    a flat slot array of length ``n_slots`` — the symbolic phase's
    capacity-bounded output layout.  ``pair_slot`` must be nondecreasing
    and pairs referencing zero blocks must be inert (both guaranteed by
    ``repro.core.symbolic``).  Returns f32[n_slots, bs, bs]; the caller
    casts to the output dtype.
    """
    prods = jnp.einsum(
        "kab,kbc->kac", a_blocks[pair_a], b_blocks[pair_b],
        preferred_element_type=jnp.float32)                        # [P, bs, bs]
    # pair_slot is nondecreasing by contract: a sorted segment reduction
    # beats a general scatter-add on CPU/GPU backends
    return jax.ops.segment_sum(prods, pair_slot, num_segments=n_slots,
                               indices_are_sorted=True)


def densify_raw(blocks, rows, cols, n_block_rows: int, n_block_cols: int):
    """Scatter a flat block list into a dense tile (SpGEMM B-side helper)."""
    cap, bs, _ = blocks.shape
    out = jnp.zeros((n_block_rows, n_block_cols, bs, bs), blocks.dtype)
    out = out.at[rows, cols].add(blocks)
    return out.transpose(0, 2, 1, 3).reshape(n_block_rows * bs, n_block_cols * bs)


def entry_decode_ref(vals, idx, shape):
    """The ``shape`` (``[slots, bs, bs]``) tile whose entries are ``vals``
    at flat positions ``idx`` (ascending, distinct): zeros with each entry
    written at its position, one scatter; entries positioned past the tile
    are dropped."""
    size = shape[0] * shape[1] * shape[2]
    flat = jnp.zeros(size, vals.dtype).at[idx].set(
        vals, indices_are_sorted=True, unique_indices=True, mode="drop")
    return flat.reshape(shape)
