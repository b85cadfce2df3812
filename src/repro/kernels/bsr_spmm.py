"""Pallas TPU kernels for local block-sparse matmul.

TPU adaptation of the paper's local cuSPARSE calls: sparsity is expressed at
MXU-block granularity (``bs x bs`` dense blocks, bs=128 in production), and
the CSR structure arrays become *scalar-prefetch* operands that steer the
BlockSpec index maps.  The grid walks the stored-block list with the reduction
innermost, so revisits of an output block are consecutive and accumulate in
VMEM (classic grouped-matmul pattern); double-buffering of the streamed A
blocks and B column panels is done by the Pallas pipeline automatically.

Three kernels:

* :func:`bsr_spmm_pallas`       — SpMM: BSR(A) @ dense(B).
* :func:`bsr_pair_matmul_pallas`— SpGEMM inner: pre-matched A/B block pairs
  accumulated into a dense C tile (host-known sparsity structure).
* :func:`bsr_pair_accumulate_pallas` — sparse-output SpGEMM inner: the same
  pre-matched pairs accumulated into *packed* output block slots (the
  symbolic phase's capacity-bounded layout), never materializing a dense C
  tile.

Scalar-prefetch lists live in SMEM, which holds 1 MiB on a TPU v5e, so
one ``pallas_call`` takes at most :func:`list_chunk` entries per list.
Longer lists arrive in the *chunked layout*: consecutive chunks of
exactly ``list_chunk(n)`` entries, each starting at a new output block
and padded with inert entries that repeat its last output block.  The
plan-time list builders (``core.symbolic``, ``core.steal3d``) lay lists
out this way; the SpMM and pair-accumulate kernels run one
``pallas_call`` per chunk, all writing into one aliased output buffer.
Because no output block spans two chunks, no chunk re-zeroes a block
that another chunk wrote.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["SMEM_LIST_BYTES", "list_chunk", "bsr_spmm_pallas",
           "bsr_pair_matmul_pallas", "bsr_pair_accumulate_pallas"]

# Bytes of int32 scalar-prefetch lists one pallas_call may hold in SMEM
# (1 MiB on v5e); the rest is left to the kernel's own scalars.
SMEM_LIST_BYTES = 512 * 1024


def list_chunk(n_lists: int) -> int:
    """Most entries per chunk when ``n_lists`` int32 lists share SMEM."""
    return SMEM_LIST_BYTES // (4 * n_lists)


def _chunked(call, lists, chunk: int):
    """Run ``call(chunk_lists, offset, out)`` over the chunked layout.

    ``offset`` is the chunk's first list position.  The first chunk
    allocates the output (``out=None``); every later chunk aliases it, so
    all chunks write into one buffer.
    """
    total = lists[0].shape[0]
    if total <= chunk:
        return call(lists, 0, None)
    if total % chunk:
        raise ValueError(
            f"list of {total} entries exceeds the SMEM budget of {chunk} "
            "entries per kernel call and is not in the chunked layout (a "
            f"multiple of {chunk}, each chunk starting at a new output "
            "block); build it with repro.core.symbolic.chunk_pair_lists")
    out = call(tuple(x[:chunk] for x in lists), 0, None)

    def body(c, out):
        off = c * chunk
        return call(tuple(lax.dynamic_slice_in_dim(x, off, chunk)
                          for x in lists), off, out)

    return lax.fori_loop(1, total // chunk, body, out)


def _with_out(in_specs, operands, out, n_prefetch: int):
    """Append the aliased output buffer (left in HBM, never read) to a
    call's inputs; returns ``(in_specs, operands, input_output_aliases)``."""
    if out is None:
        return in_specs, operands, {}
    return (in_specs + [pl.BlockSpec(memory_space=pl.ANY)],
            operands + [out], {n_prefetch + len(in_specs): 0})


# ---------------------------------------------------------------------------
# SpMM: C[rows[s]] += A_blocks[off + s] @ B[cols[s], :]
# ---------------------------------------------------------------------------
def _spmm_kernel(off_ref, rows_ref, cols_ref, a_ref, b_ref, *refs):
    c_ref = refs[-1]                  # refs[0] is the aliased input, if any
    s = pl.program_id(1)  # stored-block step (innermost)
    prev = rows_ref[jnp.maximum(s - 1, 0)]
    is_first = jnp.logical_or(s == 0, rows_ref[s] != prev)

    @pl.when(is_first)
    def _zero():
        c_ref[...] = jnp.zeros_like(c_ref)

    a = a_ref[0]                      # [bs, bs]
    b = b_ref[...]                    # [bs, bn]
    c_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("n_block_rows", "block_n", "chunked", "interpret"),
)
def bsr_spmm_pallas(blocks, rows, cols, dense, *, n_block_rows: int,
                    block_n: int = 256, chunked: bool = False,
                    interpret: bool = False):
    """C = BSR @ dense via pallas_call.

    blocks : f[cap, bs, bs] — zero-padded stored blocks, ``rows`` sorted
    rows, cols : i32[cap] — every output block-row must appear in ``rows``
                 (coverage contract: the kernel zeroes an output block on
                 first visit only; uncovered rows would return garbage).
                 ``ops.bsr_spmm_raw(augment=True)`` establishes this per
                 call; ``TiledBSR`` stores tiles pre-augmented.
    dense  : f[n_block_cols*bs, n] with n % block_n == 0
    chunked : the lists are in the chunked layout (module docstring);
              without it, lists longer than ``list_chunk(2)`` are refused.
    """
    cap, bs, _ = blocks.shape
    n = dense.shape[1]
    if n % block_n:
        raise ValueError(f"n={n} not a multiple of block_n={block_n}")
    nj = n // block_n
    chunk = list_chunk(2)
    if cap > chunk and not chunked:
        raise ValueError(
            f"{cap} stored blocks exceed the SMEM budget of {chunk} list "
            "entries per kernel call, and a tile's stored lists are not in "
            "the chunked layout; use a larger grid")

    def call(lists, off, out):
        rows_c, cols_c = lists
        in_specs, operands, aliases = _with_out(
            [pl.BlockSpec((1, bs, bs),
                          lambda j, s, o, r, c: (o[0] + s, 0, 0)),
             pl.BlockSpec((bs, block_n), lambda j, s, o, r, c: (c[s], j))],
            [blocks, dense], out, 3)
        return pl.pallas_call(
            _spmm_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,    # chunk offset, rows, cols
                # steps innermost => consecutive row visits
                grid=(nj, rows_c.shape[0]),
                in_specs=in_specs,
                out_specs=pl.BlockSpec(
                    (bs, block_n), lambda j, s, o, r, c: (r[s], j))),
            out_shape=jax.ShapeDtypeStruct((n_block_rows * bs, n),
                                           jnp.float32),
            input_output_aliases=aliases,
            interpret=interpret,
        )(jnp.full((1,), off, jnp.int32), rows_c, cols_c, *operands)

    out = _chunked(call, (rows, cols), chunk)
    return out.astype(jnp.promote_types(blocks.dtype, dense.dtype))


# ---------------------------------------------------------------------------
# SpGEMM inner: C[pr[s], pc[s]] += A_blocks[pa[s]] @ B_blocks[pb[s]]
# ---------------------------------------------------------------------------
def _pair_kernel(pa_ref, pb_ref, pr_ref, pc_ref, a_ref, b_ref, c_ref):
    s = pl.program_id(0)
    prev_r = pr_ref[jnp.maximum(s - 1, 0)]
    prev_c = pc_ref[jnp.maximum(s - 1, 0)]
    is_first = jnp.logical_or(
        s == 0,
        jnp.logical_or(pr_ref[s] != prev_r, pc_ref[s] != prev_c))

    @pl.when(is_first)
    def _zero():
        c_ref[...] = jnp.zeros_like(c_ref)

    c_ref[...] += jnp.dot(a_ref[0], b_ref[0],
                          preferred_element_type=jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("n_block_rows", "n_block_cols", "interpret"),
)
def bsr_pair_matmul_pallas(a_blocks, b_blocks, pair_a, pair_b, pair_rows,
                           pair_cols, *, n_block_rows: int, n_block_cols: int,
                           interpret: bool = False):
    """Dense C tile from pre-matched sparse block pairs (sorted by (row,col)).

    Padding pairs must reference zero blocks and repeat the final (row, col).
    """
    npairs = pair_a.shape[0]
    bs = a_blocks.shape[1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,        # pair_a, pair_b, pair_rows, pair_cols
        grid=(npairs,),
        in_specs=[
            pl.BlockSpec((1, bs, bs), lambda s, pa, pb, pr, pc: (pa[s], 0, 0)),
            pl.BlockSpec((1, bs, bs), lambda s, pa, pb, pr, pc: (pb[s], 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (bs, bs), lambda s, pa, pb, pr, pc: (pr[s], pc[s])),
    )
    out = pl.pallas_call(
        _pair_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (n_block_rows * bs, n_block_cols * bs), jnp.float32),
        interpret=interpret,
    )(pair_a, pair_b, pair_rows, pair_cols, a_blocks, b_blocks)
    return out.astype(jnp.promote_types(a_blocks.dtype, b_blocks.dtype))


# ---------------------------------------------------------------------------
# Sparse-output SpGEMM inner: C_blocks[ps[s]] += A_blocks[pa[s]] @ B_blocks[pb[s]]
# ---------------------------------------------------------------------------
def _pair_acc_kernel(pa_ref, pb_ref, ps_ref, a_ref, b_ref, *refs):
    c_ref = refs[-1]                  # refs[0] is the aliased input, if any
    s = pl.program_id(0)
    prev = ps_ref[jnp.maximum(s - 1, 0)]
    is_first = jnp.logical_or(s == 0, ps_ref[s] != prev)

    @pl.when(is_first)
    def _zero():
        c_ref[...] = jnp.zeros_like(c_ref)

    c_ref[...] += jnp.dot(a_ref[0], b_ref[0],
                          preferred_element_type=jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=("n_slots", "interpret"),
)
def bsr_pair_accumulate_pallas(a_blocks, b_blocks, pair_a, pair_b, pair_slot,
                               *, n_slots: int, interpret: bool = False):
    """Packed C blocks from pre-matched sparse block pairs.

    pair_slot : i32[P] — output slot per pair, NONDECREASING; every slot in
                ``[0, n_slots)`` must appear at least once (the symbolic
                phase emits one coverage pair per slot), because an output
                block is zeroed on its first visit only.
    Padding pairs must reference zero blocks and repeat the final slot.
    Lists longer than ``list_chunk(3)`` must be in the chunked layout
    (module docstring).
    Returns f32[n_slots, bs, bs]; the caller casts to the output dtype.
    """
    bs = a_blocks.shape[1]

    def call(lists, off, out):
        in_specs, operands, aliases = _with_out(
            [pl.BlockSpec((1, bs, bs), lambda s, pa, pb, ps: (pa[s], 0, 0)),
             pl.BlockSpec((1, bs, bs), lambda s, pa, pb, ps: (pb[s], 0, 0))],
            [a_blocks, b_blocks], out, 3)
        return pl.pallas_call(
            _pair_acc_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,    # pair_a, pair_b, pair_slot
                grid=(lists[0].shape[0],),
                in_specs=in_specs,
                out_specs=pl.BlockSpec((1, bs, bs),
                                       lambda s, pa, pb, ps: (ps[s], 0, 0))),
            out_shape=jax.ShapeDtypeStruct((n_slots, bs, bs), jnp.float32),
            input_output_aliases=aliases,
            interpret=interpret,
        )(*lists, *operands)

    return _chunked(call, (pair_a, pair_b, pair_slot), list_chunk(3))
