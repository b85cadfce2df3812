"""Public jit'd wrappers for the Pallas kernels.

``impl`` dispatch:
  * ``"pallas"``     — real TPU lowering (production target).
  * ``"interpret"``  — Pallas interpret mode (CPU validation; this container).
  * ``"ref"``        — pure-jnp oracle (used inside CPU shard_map tests and as
                       the allclose target).
  * ``"auto"``       — pallas on TPU backends, ref elsewhere.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ref as _ref
from .bsr_spmm import (bsr_pair_accumulate_pallas, bsr_pair_matmul_pallas,
                       bsr_spmm_pallas)

__all__ = [
    "default_impl", "bsr_spmm", "bsr_spmm_raw", "match_block_pairs",
    "build_pair_lists", "bsr_pair_matmul", "bsr_pair_accumulate",
    "steal_pair_accumulate", "densify", "densify_packed",
]


def default_impl() -> str:
    return "pallas" if jax.default_backend() in ("tpu",) else "ref"


def _resolve(impl: Optional[str]) -> str:
    impl = impl or "auto"
    return default_impl() if impl == "auto" else impl


# ---------------------------------------------------------------------------
# SpMM
# ---------------------------------------------------------------------------
def bsr_spmm_raw(blocks, rows, cols, dense, *, n_block_rows: int,
                 impl: Optional[str] = None, block_n: int = 256,
                 augment: bool = True, chunked: bool = False):
    """C = BSR(blocks, rows, cols) @ dense — raw-array form (shard_map-safe).

    ``augment=False`` asserts the caller's arrays are already
    coverage-augmented and row-sorted (every output block-row present —
    the :class:`repro.core.bsr.TiledBSR` storage contract), skipping the
    concat + stable-argsort below.  The distributed ring bodies rely on
    this: augmentation must not be re-traced into every scanned step.
    ``chunked=True`` asserts the lists are in the kernels' chunked layout
    (``repro.kernels.bsr_spmm``), as plan-built pair lists are.
    """
    impl = _resolve(impl)
    bs = blocks.shape[1]
    n = dense.shape[1]
    if n == 0:  # half-panel schedules can produce empty panels at tiny tn;
        # impl-independent (the ref path's reshape(-1, bs, 0) divides by 0)
        return jnp.zeros((n_block_rows * bs, 0),
                         jnp.promote_types(blocks.dtype, dense.dtype))
    if impl == "ref":
        return _ref.bsr_spmm_raw_ref(blocks, rows, cols, dense, n_block_rows)
    bn = min(block_n, n)
    while n % bn:
        bn //= 2
    if augment:
        # Coverage augmentation: append one zero block per block-row so that
        # every output block is visited (and therefore zero-initialized) by
        # the kernel, even for rows with no stored blocks.  Stable sort keeps
        # row order.
        cov = jnp.arange(n_block_rows, dtype=rows.dtype)
        rows_aug = jnp.concatenate([rows, cov])
        order = jnp.argsort(rows_aug, stable=True)
        blocks = jnp.concatenate(
            [blocks, jnp.zeros((n_block_rows, bs, bs), blocks.dtype)])[order]
        cols = jnp.concatenate(
            [cols, jnp.zeros((n_block_rows,), cols.dtype)])[order]
        rows = rows_aug[order]
    return bsr_spmm_pallas(blocks, rows, cols, dense,
                           n_block_rows=n_block_rows, block_n=max(bn, 1),
                           chunked=chunked, interpret=(impl == "interpret"))


def bsr_spmm(a_bsr, dense, *, impl: Optional[str] = None, block_n: int = 256):
    """C = A @ dense for a :class:`repro.core.bsr.BSR` A."""
    return bsr_spmm_raw(a_bsr.blocks, a_bsr.rows, a_bsr.cols, dense,
                        n_block_rows=a_bsr.n_block_rows, impl=impl,
                        block_n=block_n)


# ---------------------------------------------------------------------------
# SpGEMM (host-known structure): pair-list construction + kernel
# ---------------------------------------------------------------------------
def match_block_pairs(a_cols, b_rows):
    """Vectorized sort-merge join on ``a_cols[i] == b_rows[j]`` (host numpy).

    The core of the SpGEMM symbolic phase: every (A block, B block) pair
    whose product contributes to C.  Returns ``(ai, bj)`` index arrays into
    the given lists; within one A block, matched B blocks keep their
    original order (the stable argsort), matching the insertion order of
    the legacy dict-of-lists construction.  Shared by
    :func:`build_pair_lists` (dense-tile SpGEMM) and
    ``repro.core.symbolic`` (distributed sparse-output SpGEMM).
    """
    a_cols = np.asarray(a_cols, dtype=np.int64)
    b_rows = np.asarray(b_rows, dtype=np.int64)
    b_order = np.argsort(b_rows, kind="stable")
    b_rows_sorted = b_rows[b_order]
    starts = np.searchsorted(b_rows_sorted, a_cols, side="left")
    ends = np.searchsorted(b_rows_sorted, a_cols, side="right")
    deg = ends - starts
    ai = np.repeat(np.arange(len(a_cols), dtype=np.int64), deg)
    offs = np.arange(deg.sum(), dtype=np.int64) - np.repeat(
        np.cumsum(deg) - deg, deg)
    bj = b_order[np.repeat(starts, deg) + offs]
    return ai, bj


def build_pair_lists(a_rows, a_cols, a_nnzb: int, b_rows, b_cols, b_nnzb: int,
                     n_block_rows: int, n_block_cols: int,
                     capacity: Optional[int] = None
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, int]:
    """Host-side symbolic phase of block SpGEMM.

    Matches stored blocks of A and B with ``a_cols[i] == b_rows[j]`` and emits
    flat pair lists sorted by output block (row, col).  Every output block is
    covered at least once (uncovered blocks get a dummy pair referencing the
    zero slot appended by :func:`bsr_pair_matmul`), so the Pallas kernel's
    first-visit zeroing covers the whole C tile.

    Returns (pair_a, pair_b, pair_rows, pair_cols, n_real_pairs); index
    ``len(a_blocks)`` / ``len(b_blocks)`` denotes the appended zero slot.
    """
    a_rows = np.asarray(a_rows)[:a_nnzb].astype(np.int64)
    a_cols = np.asarray(a_cols)[:a_nnzb].astype(np.int64)
    b_rows = np.asarray(b_rows)[:b_nnzb].astype(np.int64)
    b_cols = np.asarray(b_cols)[:b_nnzb].astype(np.int64)
    # Vectorized sort-merge join on a_cols == b_rows (replaces the python
    # dict-of-lists construction; ~11x faster at 5k stored blocks, growing
    # with the pair count — see benchmarks/kernels_bench.py).
    ai, bj = match_block_pairs(a_cols, b_rows)
    rows = a_rows[ai]
    cols = b_cols[bj]
    # Coverage: dummy pairs (referencing the appended zero slots) for output
    # blocks no real product touches, in row-major order like the real pairs.
    zslot_a, zslot_b = a_nnzb, b_nnzb  # remapped to zero slot by the wrapper
    covered = np.zeros((n_block_rows, n_block_cols), dtype=bool)
    covered[rows, cols] = True
    ur, uc = np.nonzero(~covered)
    pair_rows = np.concatenate([rows, ur])
    pair_cols = np.concatenate([cols, uc])
    pair_a = np.concatenate([ai, np.full(len(ur), zslot_a, np.int64)])
    pair_b = np.concatenate([bj, np.full(len(ur), zslot_b, np.int64)])
    # Final stable sort by output block (row, col); the trailing position key
    # pins tie order to construction order (lexsort alone is stable, but be
    # explicit — the kernel's first-visit zeroing depends only on grouping,
    # the exact tie order is part of the legacy output contract).
    order = np.lexsort((np.arange(len(pair_rows)), pair_cols, pair_rows))
    pair_a, pair_b = pair_a[order], pair_b[order]
    pair_rows, pair_cols = pair_rows[order], pair_cols[order]
    n_real = len(pair_rows)
    cap = capacity if capacity is not None else n_real
    if n_real > cap:
        raise ValueError(f"pair capacity {cap} < required {n_real}")
    pad = cap - n_real
    pair_rows = np.concatenate([pair_rows, np.full(pad, pair_rows[-1])])
    pair_cols = np.concatenate([pair_cols, np.full(pad, pair_cols[-1])])
    pair_a = np.concatenate([pair_a, np.full(pad, zslot_a, np.int64)])
    pair_b = np.concatenate([pair_b, np.full(pad, zslot_b, np.int64)])
    return (pair_a.astype(np.int32), pair_b.astype(np.int32),
            pair_rows.astype(np.int32), pair_cols.astype(np.int32), n_real)


def bsr_pair_matmul(a_blocks, b_blocks, pair_a, pair_b, pair_rows, pair_cols,
                    *, n_block_rows: int, n_block_cols: int,
                    impl: Optional[str] = None):
    """Dense C tile from matched block pairs (see :func:`build_pair_lists`)."""
    impl = _resolve(impl)
    bs = a_blocks.shape[1]
    zero = jnp.zeros((1, bs, bs), a_blocks.dtype)
    a_ext = jnp.concatenate([a_blocks, zero.astype(a_blocks.dtype)])
    b_ext = jnp.concatenate([b_blocks, zero.astype(b_blocks.dtype)])
    if impl == "ref":
        return _ref.bsr_pair_matmul_raw_ref(
            a_ext, b_ext, pair_a, pair_b, pair_rows, pair_cols,
            n_block_rows, n_block_cols)
    return bsr_pair_matmul_pallas(
        a_ext, b_ext, pair_a, pair_b, pair_rows, pair_cols,
        n_block_rows=n_block_rows, n_block_cols=n_block_cols,
        interpret=(impl == "interpret"))


def bsr_pair_accumulate(a_blocks, b_blocks, pair_a, pair_b, pair_slot, *,
                        n_slots: int, out_dtype=None,
                        impl: Optional[str] = None):
    """Packed C blocks from matched pairs — the sparse-output SpGEMM inner.

    Unlike :func:`bsr_pair_matmul`, products accumulate into a flat
    ``[n_slots, bs, bs]`` slot array (the symbolic phase's capacity-bounded
    output layout) instead of a dense C tile.  Contract (established by
    ``repro.core.symbolic``): ``pair_slot`` is nondecreasing, every slot is
    visited at least once (coverage pairs), and dummy pairs reference zero
    blocks.  No zero slot is appended here — the operand tiles' own zero
    (coverage) blocks serve as the dummy targets, keeping the scanned ring
    step concat-free.

    ``pair_a``/``pair_b`` may index the operands' stored (padded) layout
    or the packed wire layout of ``repro.core.wire`` — the receiver-side
    slot mapping is composed into the lists at plan time
    (``wire.remap_pairs_packed``), so packed buffers are consumed with no
    unpack copy and this kernel stays layout-agnostic.
    """
    impl = _resolve(impl)
    out_dtype = out_dtype or jnp.promote_types(a_blocks.dtype, b_blocks.dtype)
    if impl == "ref":
        out = _ref.bsr_pair_accumulate_raw_ref(
            a_blocks, b_blocks, pair_a, pair_b, pair_slot, n_slots)
    else:
        out = bsr_pair_accumulate_pallas(
            a_blocks, b_blocks, pair_a, pair_b, pair_slot, n_slots=n_slots,
            interpret=(impl == "interpret"))
    return out.astype(out_dtype)


def steal_pair_accumulate(a_pool, b_rows, pair_a, pair_b, pair_slot, *,
                          n_slots: int, impl: Optional[str] = None,
                          block_n: int = 256):
    """Packed partial-C accumulation for the steal3d static dispatch.

    ``a_pool`` is a device's pooled A blocks (row panel + moved tiles +
    trailing zero block), ``b_rows`` its pooled dense B panel flattened to
    bs-row chunks.  Each pair multiplies ``a_pool[pair_a[p]]`` against
    chunk ``pair_b[p]`` and accumulates the [bs, n] product into output
    row-block ``pair_slot[p]`` — exactly the :func:`bsr_spmm_raw` contract
    with plan-built pair lists (``repro.core.steal3d``) standing in for a
    tile's stored structure, so every impl path (ref / interpret / pallas)
    is reused unchanged.  Contract: ``pair_slot`` nondecreasing, every
    slot visited at least once (coverage pairs), dummy pairs reference the
    zero block, and lists past the SMEM budget in the chunked layout.
    """
    return bsr_spmm_raw(a_pool[pair_a], pair_slot, pair_b, b_rows,
                        n_block_rows=n_slots, impl=impl, block_n=block_n,
                        augment=False, chunked=True)


def densify(blocks, rows, cols, *, n_block_rows: int, n_block_cols: int):
    return _ref.densify_raw(blocks, rows, cols, n_block_rows, n_block_cols)


def densify_packed(blocks, dmap, *, n_block_rows: int, n_block_cols: int):
    """Dense tile from packed wire blocks via a static *gather*.

    ``dmap`` (built by ``repro.core.wire``) maps every dense block
    position, row-major, to the packed slot holding its data — or to a
    guaranteed-zero slot for structurally empty positions.  This is the
    packed-wire replacement for :func:`densify` inside scanned ring steps:
    structure is plan-time static, so the scatter of ``densify_raw``
    becomes a gather + transpose and the hot-loop jaxpr stays
    sort/scatter-free (the invariant ``tests/test_api.py`` asserts).
    """
    bs = blocks.shape[-1]
    d = blocks[dmap].reshape(n_block_rows, n_block_cols, bs, bs)
    return d.transpose(0, 2, 1, 3).reshape(n_block_rows * bs,
                                           n_block_cols * bs)
