"""The schedule layer: the shard_map bodies every registered algorithm runs.

The family: ``summa_bcast`` / ``summa_ag`` are the bulk-synchronous
baselines, ``ring_c`` / ``ring_a`` the RDMA-style stationary-C /
stationary-A rings with placement-time ``k_offset`` skew and prefetch via
early ``ppermute``, ``ring_c_bidir`` a bidirectional stationary-C ring that
splits the output into column half-panels circulating in opposite
directions (full-duplex links), and ``steal3d`` the static realization of
the paper's SS3.4 locality-aware work stealing: a plan-time LPT assignment
of the 3D (i, k, j) work grid (:mod:`repro.core.steal3d`) executed as
per-device pair lists with static moved-tile and owner-reduction ppermute
rounds.

Ring and broadcast schedules are each written once, as a driver —
:func:`ring`, :func:`bcast_steps`, :func:`gather_steps` — and each such
body is a call of one: it supplies what rides, ``consume`` (one step's
local product) and ``xs`` (per-step consume maps or pair lists); the
driver owns the prefetch, the split-step double buffer under
``geom.overlap``, the epilogue and g = 1.  An algorithm has up to three
bodies: padded (``_body_*(a, b, geom)``), packed-wire dense-output
(``_packed_body_*(a, b, aux, geom)``) and sparse-output
(``_sparse_body_*(a, b, pairs, geom)``).  :mod:`repro.core.api` registers
them; this module imports nothing from it.

Two hot-loop invariants the bodies keep (asserted by the jaxpr tests):
sparse A tiles arrive *pre-augmented* from
:class:`~repro.core.bsr.TiledBSR` (no coverage concat+sort inside a
scanned step), and sparse B tiles never scatter inside a scan — padded
bodies densify once before it (``_densify_b``), packed bodies per step by
a static *gather* (``ops.densify_packed``).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..kernels import ops as kops
from ..kernels import ref as kref


@dataclasses.dataclass(frozen=True)
class _Geom:
    """Static geometry threaded to the shard_map bodies via closure."""
    g: int
    tm: int           # local C tile rows
    tn: int           # local C tile cols
    a_nbr: int        # block-rows per A tile (0 => dense A)
    b_nbr: int        # block-rows per B tile (0 => dense B)
    b_nbc: int        # block-cols per B tile (0 => dense B)
    impl: Optional[str]
    axr: str
    axc: str
    out_dtype: object
    c_store: int = 0  # packed C slots per tile (sparse-output plans only)
    overlap: bool = False
    # split-step double-buffered bodies (plan_matmul(overlap=...)): each
    # scanned step issues step t+1's collective BEFORE step t's
    # accumulate, carrying a two-slot buffer per stream, so XLA's async
    # collectives can hide the transfer under the local matmul


# ---------------------------------------------------------------------------
# Local tile math (operand trees hold ONLY arrays)
# ---------------------------------------------------------------------------
def _densify_b(b: Dict, geom: _Geom) -> Dict:
    """Densify a sparse B tile ONCE, before the scanned ring steps.

    Every schedule consumes B as a dense tile; doing the scatter here means
    each B tile is densified at most once per ring pass, and the scanned
    step body stays free of scatter/sort work (asserted by the jaxpr test).
    The densified tile is also what rides the wire — see ``_cost_model``.
    """
    if "dense" in b:
        return b
    return {"dense": kref.densify_raw(b["blocks"], b["rows"], b["cols"],
                                      geom.b_nbr, geom.b_nbc)}


def _local_mm(a: Dict, b: Dict, geom: _Geom) -> jnp.ndarray:
    b_dense = b["dense"]    # bodies pre-densify sparse B via _densify_b
    if "dense" in a:
        out = jnp.dot(a["dense"], b_dense, preferred_element_type=jnp.float32)
    else:
        # TiledBSR tiles are pre-augmented/pre-sorted at tiling time, so the
        # kernel wrapper must not redo coverage inside the compiled loop.
        out = kops.bsr_spmm_raw(a["blocks"], a["rows"], a["cols"], b_dense,
                                n_block_rows=geom.a_nbr, impl=geom.impl,
                                augment=False)
    return out.astype(geom.out_dtype)


# The packed wire format (plan_matmul(wire="packed")) ships ONLY real
# blocks: a sparse A tile rides as a packed [wire_capacity, bs, bs] buffer
# (no coverage blocks, no rows/cols index traffic), a sparse B tile
# likewise, densified on the consumer by a static *gather* instead of the
# pre-scan scatter.  All structure lives in plan-time consume maps
# (repro.core.wire, the ``aux`` tree) riding as per-step inputs — local
# data, never on the network — so the scanned steps stay sort/scatter-free.
def _packed_a_mm(a_blocks, gidx, rows, cols, b_dense, geom: _Geom):
    """One packed local SpMM step: gather the coverage-augmented block
    list out of the packed buffer, then the standard augment-free kernel."""
    return _local_mm({"blocks": a_blocks[gidx], "rows": rows, "cols": cols},
                     {"dense": b_dense}, geom)


def _packed_b_dense(b_buf, dmap, geom: _Geom):
    return kops.densify_packed(b_buf, dmap, n_block_rows=geom.b_nbr,
                               n_block_cols=geom.b_nbc)


def _packed_operands(b: Dict, aux: Dict, geom: _Geom):
    """B as it rides a packed-wire body (its packed buffer where the plan
    packs it, else its densified tile) and the per-step consume maps."""
    b_packed = "b_dmap" in aux
    b0 = b["blocks"] if b_packed else _densify_b(b, geom)["dense"]
    xs = {"ag": aux["a_gidx"], "ar": aux["a_rows"], "ac": aux["a_cols"]}
    if b_packed:
        xs["bd"] = aux["b_dmap"]
    return b0, xs


def _packed_mm(c, a_blk, b_buf, x, geom: _Geom):
    """Consume of a packed-wire step: B gather-densified where it rides
    packed (its consume map ``x["bd"]``), then the packed A product."""
    b_dense = _packed_b_dense(b_buf, x["bd"], geom) if "bd" in x else b_buf
    return c + _packed_a_mm(a_blk, x["ag"], x["ar"], x["ac"], b_dense, geom)


# The numeric phase of symbolic/numeric SpGEMM (plan_matmul(output=
# "sparse")): both operands stay in stored block form (only ``blocks``
# rides the wire — the pair lists encode all structure), and each step
# scatter-accumulates matched block products into the packed output slots
# the symbolic phase allocated.  No dense C tile, and no densified B, ever
# materializes on a device.
def _sparse_step(c, a_t: Dict, b_t: Dict, x: Dict, geom: _Geom):
    """Consume of a sparse-output step: one pair-accumulate over its pair
    lists ``x``."""
    return c + kops.bsr_pair_accumulate(
        a_t["blocks"], b_t["blocks"], x["pa"], x["pb"], x["ps"],
        n_slots=geom.c_store, out_dtype=jnp.float32, impl=geom.impl)


def _sparse_c0(a: Dict, geom: _Geom):
    bs = a["blocks"].shape[-1]
    return _pvary(jnp.zeros((geom.c_store, bs, bs), jnp.float32), geom)


def _zeros_c(geom: _Geom, cols: Optional[int] = None):
    """A zero C tile, or a ``cols``-wide panel of one."""
    return _pvary(jnp.zeros((geom.tm, geom.tn if cols is None else cols),
                            dtype=geom.out_dtype), geom)


def _pvary(x, geom: _Geom):
    return lax.pcast(x, (geom.axr, geom.axc), to="varying")


# ---------------------------------------------------------------------------
# Collectives on a payload: a dict of arrays (in its own key order) or one
# array
# ---------------------------------------------------------------------------
def _each(fn: Callable, payload):
    if not isinstance(payload, dict):
        return fn(payload)
    return {k: fn(v) for k, v in payload.items()}


def _ring_perm(g: int, sign: int = 1):
    """One hop of a ring of g devices: each receives from its ``+sign``
    neighbour.  Every ring body's shifts use it, and
    ``schedule.ppermute-bijection`` verifies it."""
    return [((d + sign) % g, d) for d in range(g)]


def _tree_ppermute(tree, axis: str, g: int, sign: int = 1):
    perm = _ring_perm(g, sign)
    return _each(lambda v: lax.ppermute(v, axis, perm), tree)


def _tree_bcast(tree, axis: str, root, my_idx):
    sel = my_idx == root
    return _each(
        lambda v: lax.psum(jnp.where(sel, v, jnp.zeros_like(v)), axis), tree)


# ---------------------------------------------------------------------------
# The drivers
# ---------------------------------------------------------------------------
def _ring_steps(g: int, overlap: bool) -> int:
    """Scanned steps of a ring: every step but those after the last
    transfer (one, or two with the two-slot buffer), which run as the
    epilogue, so no step sends a tile that none consumes."""
    return max(g - 2, 0) if overlap else g - 1


def _split_steps(xs: Dict, n: int, g: int) -> Tuple[Dict, list]:
    """Per-step inputs ``xs`` (leading axis g) split into the scanned
    steps' ``[:n]`` and one dict per epilogue step ``n .. g-1``."""
    return ({k: v[:n] for k, v in xs.items()},
            [{k: v[t] for k, v in xs.items()} for t in range(n, g)])


def ring(streams: Sequence[Tuple[object, str, int]], consume: Callable, c0,
         xs: Dict, geom: _Geom):
    """Run a ring schedule; return its accumulator.

    ``streams`` holds ``(payload, mesh axis, direction)``: each step every
    payload moves one hop (device d receives from d + direction), g - 1
    shifts in all.  ``consume(c, tiles, x)`` folds one generation of tiles
    (a tuple in stream order) into ``c`` (an array or a tuple); generation
    t meets ``xs[t]``, for t = 0 .. g - 1.

    A scanned step issues the shift of the newest tiles it holds before
    it consumes the oldest ("async_get_tile", paper SS3.3), so the DMA
    overlaps the local product.  Bulk form: the carry holds one
    generation, fetched the step before.  Split-step form
    (``geom.overlap``): it also holds the one in flight, so the transfer
    consumed at step t+1 was issued at step t-1 — a full local product of
    slack; the prologue issues step 1's transfer.  The scan runs the steps
    that still have a tile to issue, and the epilogue consumes the
    generations left in the carry.
    """
    g = geom.g

    def shift(gen):
        return tuple(_tree_ppermute(t, axis, g, sign)
                     for t, (_, axis, sign) in zip(gen, streams))

    n = _ring_steps(g, geom.overlap)
    xs, tail = _split_steps(xs, n, g)
    held = (tuple(payload for payload, _, _ in streams),)
    if geom.overlap:     # the prologue (at g = 1 nothing shifts)
        held += (shift(held[0]) if g > 1 else held[0],)

    def step(carry, x):
        held, c = carry
        nxt = shift(held[-1])
        return (held[1:] + (nxt,), consume(c, held[0], x)), None

    (held, c), _ = lax.scan(step, (held, c0), xs, length=n)
    for gen, x in zip(held, tail):
        c = consume(c, gen, x)
    return c


def bcast_steps(bcast: Callable, consume: Callable, c0, xs: Dict,
                geom: _Geom):
    """Run a broadcast schedule (SUMMA); return its accumulator.

    ``bcast(k)`` returns inner step k's panels on every device that needs
    them; ``consume(c, panels, x)`` folds them into ``c`` with ``xs[k]``.
    Bulk form: each step broadcasts, then consumes.  Split-step form
    (``geom.overlap``): step k broadcasts step k+1's panels before
    consuming its carried ones, so the collective overlaps the local
    product; the prologue broadcasts step 0's, and the last panels are
    consumed after the scan.
    """
    held, tail = (), []
    if geom.overlap:
        xs, tail = _split_steps(xs, geom.g - 1, geom.g)
        held = (bcast(jnp.int32(0)),)

    def step(carry, kx):
        held, c = carry
        k, x = kx
        held += (bcast(k),)
        return (held[1:], consume(c, held[0], x)), None

    (held, c), _ = lax.scan(step, (held, c0),
                            (jnp.arange(len(held), geom.g), xs))
    for panels, x in zip(held, tail):
        c = consume(c, panels, x)
    return c


def _summa_bcast(a, b, geom: _Geom) -> Callable:
    """SUMMA's ``bcast(k)``: A's tile of grid column k along each grid row
    and B's of grid row k along each grid column."""
    my_row = lax.axis_index(geom.axr)
    my_col = lax.axis_index(geom.axc)
    return lambda k: (_tree_bcast(a, geom.axc, k, my_col),
                      _tree_bcast(b, geom.axr, k, my_row))


def gather_steps(a, b, consume: Callable, c0, xs: Dict, geom: _Geom):
    """Run the all-gather SUMMA: one up-front all-gather of A's grid-row
    panel and B's grid-column panel (each leaf gains a leading axis g),
    then g local steps, ``consume(c, a_g, b_g, k, xs[k])``.  No split-step
    form: every step depends on the one up-front gather, so there is no
    per-step transfer to double-buffer."""
    a_g = _each(lambda v: lax.all_gather(v, geom.axc), a)
    b_g = _each(lambda v: lax.all_gather(v, geom.axr), b)

    def step(c, kx):
        k, x = kx
        return consume(c, a_g, b_g, k, x), None

    c, _ = lax.scan(step, c0, (jnp.arange(geom.g), xs))
    return c


def _at(tree: Dict, k) -> Dict:
    """Inner step k's tile out of a gathered panel."""
    return {kk: v[k] for kk, v in tree.items()}


# ---------------------------------------------------------------------------
# Ring bodies
# ---------------------------------------------------------------------------
def _entry_ring_c(a, b, geom: _Geom):
    """Paper Alg 2 with A's tile shipped as its nonzero entries.

    ``a`` holds the device's own tile (``blocks``, ``rows``, ``cols``) and
    its encoding (``vals``, ``idx``; :mod:`repro.core.entries`).  Each step
    issues the shift of the encoded tile (with its rows and cols) and of B
    before the local matmul, then rebuilds the received tile, bit for bit
    (``kref.entry_decode_ref``, one XLA scatter), for the next step; the
    encoded form travels on round the ring and is never encoded again.  The g - 1 steps are
    written out one by one, not scanned: each ends in the decode of the
    tile just received.  One form serves both ``overlap`` modes: every
    shift is issued a full local matmul before its tile is used, and at
    g = 2 the split-step and bulk bodies are the same program.
    """
    b = _densify_b(b, geom)
    wire = {k: a[k] for k in ("vals", "idx", "rows", "cols")}  # float first:
    tile = {k: a[k] for k in ("blocks", "rows", "cols")}       # one message
    c = _pvary(jnp.zeros((geom.tm, geom.tn), dtype=geom.out_dtype), geom)
    for _ in range(geom.g - 1):
        wire = _tree_ppermute(wire, geom.axc, geom.g)
        b_n = _tree_ppermute(b, geom.axr, geom.g)
        c = c + _local_mm(tile, b, geom)
        tile = {"blocks": kref.entry_decode_ref(wire["vals"], wire["idx"],
                                                a["blocks"].shape),
                "rows": wire["rows"], "cols": wire["cols"]}
        b = b_n
    return c + _local_mm(tile, b, geom)


def _body_ring_c(a, b, geom: _Geom):
    """Paper Alg 2 (stationary-C): skewed placement + neighbour ppermute.
    An A tree holding its tile's entries (``"vals"``, ``wire="entries"``)
    ships them in place of the blocks (:func:`_entry_ring_c`)."""
    if "vals" in a:
        return _entry_ring_c(a, b, geom)
    b = _densify_b(b, geom)
    return ring(((a, geom.axc, 1), (b, geom.axr, 1)),
                lambda c, ts, _: c + _local_mm(*ts, geom), _zeros_c(geom),
                {}, geom)


def _packed_body_ring_c(a, b, aux, geom: _Geom):
    """Stationary-C ring over packed wire buffers (paper Alg 2)."""
    b0, xs = _packed_operands(b, aux, geom)
    return ring(((a["blocks"], geom.axc, 1), (b0, geom.axr, 1)),
                lambda c, ts, x: _packed_mm(c, *ts, x, geom),
                _zeros_c(geom), xs, geom)


def _sparse_body_ring_c(a, b, pairs, geom: _Geom):
    """Stationary-C ring with packed sparse output: the skewed placement
    of ``ring_c``, B riding in stored block form (its densified tile never
    exists), step t consuming the step-scheduled pair lists ``pairs[t]``."""
    c = ring(((a, geom.axc, 1), (b, geom.axr, 1)),
             lambda c, ts, x: _sparse_step(c, *ts, x, geom),
             _sparse_c0(a, geom), pairs, geom)
    return c.astype(geom.out_dtype)


def _bidir(a, b, mm, xs, geom: _Geom):
    """``ring_c_bidir``'s ring: A rides both ways, the left column
    half-panel of B (densified) the +1 ring and its right one the -1 ring;
    ``mm(a_t, b_t, x, side)`` is one half-panel's product (``side`` "f"
    left, "b" right)."""
    b = _densify_b(b, geom)["dense"]
    half = geom.tn // 2

    def consume(c, ts, x):
        a_f, a_b, b_f, b_b = ts
        return c[0] + mm(a_f, b_f, x, "f"), c[1] + mm(a_b, b_b, x, "b")

    c_l, c_r = ring(((a, geom.axc, 1), (a, geom.axc, -1),
                     (b[:, :half], geom.axr, 1), (b[:, half:], geom.axr, -1)),
                    consume,
                    (_zeros_c(geom, half), _zeros_c(geom, geom.tn - half)),
                    xs, geom)
    return jnp.concatenate([c_l, c_r], axis=1)


def _body_ring_c_bidir(a, b, geom: _Geom):
    """Bidirectional stationary-C ring: C split into column half-panels.

    The left half-panel's operands (the full A tile + the left half of the
    dense B tile) ride the +1 ring computing ``k = i+j+t``; the right
    half-panel's ride the -1 ring computing ``k = i+j-t``.  Both start from
    the same skewed placement as ``ring_c``, so no new placement state is
    materialized.  The two streams use opposite directions of the
    full-duplex torus links concurrently, halving B's serialized wire time
    at the cost of shipping A both ways — a genuinely different
    comm/compute trade for ``algorithm="auto"`` (wins for sparse-A x wide-B
    SpMM, loses when A's tile bytes dominate).
    """
    return _bidir(a, b, lambda a_t, b_t, _, side: _local_mm(
        a_t, {"dense": b_t}, geom), {}, geom)


def _packed_body_ring_c_bidir(a, b, aux, geom: _Geom):
    """Bidirectional stationary-C ring, A packed in both directions.

    B's column half-panels are not block-aligned (tn // 2 need not be a
    block multiple), so B rides densified as in the padded body; only the
    A streams — the bidir schedule's doubled wire term — pack.
    """
    xs = {"fg": aux["a_gidx"], "fr": aux["a_rows"], "fc": aux["a_cols"],
          "bg": aux["a_gidx_bwd"], "br": aux["a_rows_bwd"],
          "bc": aux["a_cols_bwd"]}

    def mm(a_t, b_t, x, side):
        return _packed_a_mm(a_t, x[side + "g"], x[side + "r"],
                            x[side + "c"], b_t, geom)

    return _bidir(a["blocks"], b, mm, xs, geom)


def _hop(acc, geom: _Geom):
    """``ring_a``'s partial C tile one hop toward its owner (the TPU
    replacement for the paper's remote accumulation queue push): g hops in
    all, the last one home.  A hop depends on the accumulate it follows
    (the ride-home chain is serial), so it stays in the consume and is
    never double-buffered."""
    return lax.ppermute(acc, geom.axc, _ring_perm(geom.g))


def _body_ring_a(a, b, geom: _Geom):
    """Paper Alg 1 (stationary-A): B rides the ring, partial C rides back."""
    return ring(((_densify_b(b, geom), geom.axr, 1),),
                lambda acc, ts, _: _hop(acc + _local_mm(a, ts[0], geom), geom),
                _zeros_c(geom), {}, geom)


def _packed_body_ring_a(a, b, aux, geom: _Geom):
    """Stationary-A ring with the sparse B operand packed on the wire.

    A never moves (nothing to pack); the win is B riding as real blocks
    instead of a densified tile, gather-densified each step.  Partial C
    tiles still ride back dense — their structure differs per hop (the
    ROADMAP's sparse-output ring_a item).
    """
    def mm_hop(acc, ts, x):
        b_t = {"dense": _packed_b_dense(ts[0], x["bd"], geom)}
        return _hop(acc + _local_mm(a, b_t, geom), geom)

    return ring(((b["blocks"], geom.axr, 1),), mm_hop, _zeros_c(geom),
                {"bd": aux["b_dmap"]}, geom)


# ---------------------------------------------------------------------------
# SUMMA bodies
# ---------------------------------------------------------------------------
def _body_summa_bcast(a, b, geom: _Geom):
    """Bulk-synchronous SUMMA (paper SS2.2): a broadcast per inner step."""
    b = _densify_b(b, geom)
    return bcast_steps(_summa_bcast(a, b, geom),
                       lambda c, ts, _: c + _local_mm(*ts, geom),
                       _zeros_c(geom), {}, geom)


def _packed_body_summa_bcast(a, b, aux, geom: _Geom):
    """Bulk-synchronous SUMMA broadcasting packed buffers per inner step."""
    b0, xs = _packed_operands(b, aux, geom)
    return bcast_steps(_summa_bcast(a["blocks"], b0, geom),
                       lambda c, ts, x: _packed_mm(c, *ts, x, geom),
                       _zeros_c(geom), xs, geom)


def _sparse_body_summa_bcast(a, b, pairs, geom: _Geom):
    """Bulk-synchronous SUMMA with packed sparse output."""
    c = bcast_steps(_summa_bcast(a, b, geom),
                    lambda c, ts, x: _sparse_step(c, *ts, x, geom),
                    _sparse_c0(a, geom), pairs, geom)
    return c.astype(geom.out_dtype)


def _body_summa_ag(a, b, geom: _Geom):
    """All-gather SUMMA: one big up-front collective, g x tile footprint
    (wire-amortized: no overlap form, see :func:`gather_steps`)."""
    return gather_steps(
        a, _densify_b(b, geom),
        lambda c, a_g, b_g, k, _: c + _local_mm(_at(a_g, k), _at(b_g, k),
                                                geom),
        _zeros_c(geom), {}, geom)


def _packed_body_summa_ag(a, b, aux, geom: _Geom):
    """All-gather SUMMA over packed panels (per-source packed segments):
    a consume map indexes the flattened pool of all g packed tiles."""
    b0, xs = _packed_operands(b, aux, geom)

    def flat(pool):              # [g, wc, bs, bs] -> [g * wc, bs, bs]
        return pool.reshape((-1,) + pool.shape[-2:])

    def mm(c, a_pool, b_g, k, x):   # B packed, or step k's densified tile
        return _packed_mm(c, flat(a_pool), flat(b_g) if "bd" in x else b_g[k],
                          x, geom)

    return gather_steps(a["blocks"], b0, mm, _zeros_c(geom), xs, geom)


def _sparse_body_summa_ag(a, b, pairs, geom: _Geom):
    """All-gather SUMMA with packed sparse output."""
    c = gather_steps(
        a, b, lambda c, a_g, b_g, k, x: _sparse_step(c, _at(a_g, k),
                                                   _at(b_g, k), x, geom),
        _sparse_c0(a, geom), pairs, geom)
    return c.astype(geom.out_dtype)


# ---------------------------------------------------------------------------
# steal3d: static 3D work-grid dispatch from the stealing equilibrium
# ---------------------------------------------------------------------------
def _steal3d_perm(g: int, delta: int):
    return [(d, (d + delta) % g) for d in range(g)]


def _body_steal3d(a, b, aux, geom: _Geom, splan):
    """Static realization of the paper's SS3.4 locality-aware work stealing.

    Executes the plan-time LPT assignment of (i, k, j) items: each device
    all-gathers its A grid-row panel and (densified) B grid-column panel,
    receives the moved tiles of its off-owner items in static ppermute
    rounds, runs ONE packed pair-accumulate over its item list (length =
    the stealing equilibrium's makespan, not the uniform g x capacity of
    the owner-computes rings), and ships partial C tiles home in static
    reduce rounds.  No scan: the whole dispatch is one flat program.

    Under ``splan.wire == "packed"`` (sparse A) every A-side shipment
    carries only real blocks: the panel gather rides at the packed wire
    capacity, each moved-tile round is sliced to its own per-move real
    max (the packed prefix makes that a slice, not a gather), and the
    partial-C reduce rounds ship only the block-rows their items can
    touch, scatter-added into the owner's tile outside any scan.
    """
    g = geom.g
    packed = splan.wire == "packed"
    if splan.a_kind == "bsr":
        a_tiles = lax.all_gather(a["blocks"], geom.axc)  # [g, stride, bs, bs]
    else:
        a_tiles = lax.all_gather(a["dense"], geom.axc)   # [g, tm, tk]
    b_dense = _densify_b(b, geom)["dense"]
    b_tiles = lax.all_gather(b_dense, geom.axr)          # [g, tk, tn]
    # moved tiles: one ppermute round per hop distance, source-side static
    # gather indices select what each source packs (paper's "one moving
    # tile" for locality-constrained steals).  Issued here, before any
    # accumulate — on the overlap path (splan.overlap) the own-item
    # segment depends only on the panel gathers, so these transfers fly
    # while it computes.
    if packed:
        # flat segments: strides differ per round (per-move real max)
        moved_a = [
            lax.ppermute(a_tiles[aux[f"amk{delta}"]][:, :rcap], geom.axr,
                         _steal3d_perm(g, delta))
            .reshape((-1,) + a_tiles.shape[-2:])
            for delta, rcap in zip(splan.a_deltas, splan.a_round_cap)]
    else:
        moved_a = [lax.ppermute(a_tiles[aux[f"amk{delta}"]], geom.axr,
                                _steal3d_perm(g, delta))
                   for delta in splan.a_deltas]
    moved_b = [lax.ppermute(b_tiles[aux[f"bmk{delta}"]], geom.axc,
                            _steal3d_perm(g, delta))
               for delta in splan.b_deltas]
    if packed:
        panel_a = a_tiles.reshape((-1,) + a_tiles.shape[-2:])
        zero_a = _pvary(jnp.zeros((1,) + a_tiles.shape[-2:],
                                  a_tiles.dtype), geom)
    else:
        panel_a = a_tiles
        zero_a = _pvary(jnp.zeros((1,) + a_tiles.shape[1:],
                                  a_tiles.dtype), geom)
    a_pool = jnp.concatenate([panel_a] + moved_a + [zero_a])
    b_pool = jnp.concatenate([b_tiles] + moved_b) if moved_b else b_tiles

    def _accum(a_p, b_p, pa, pb, ps):
        if splan.a_kind == "bsr":
            blocks = a_p if packed else a_p.reshape((-1,) + a_p.shape[-2:])
            b_flat = b_p.reshape(-1, b_p.shape[-1])
            cc = kops.steal_pair_accumulate(blocks, b_flat, pa, pb, ps,
                                            n_slots=splan.n_slots,
                                            impl=geom.impl)
            return cc.reshape(splan.n_out, geom.tm, geom.tn)
        prods = jnp.einsum("pij,pjk->pik", a_p[pa], b_p[pb],
                           preferred_element_type=jnp.float32)
        return jax.ops.segment_sum(prods, ps, num_segments=splan.n_out,
                                   indices_are_sorted=True)

    if splan.overlap:
        # two-segment split: own items (panel-only pool, zero block right
        # after the g panel tiles) accumulate while the moved-tile rounds
        # are in flight; stolen items consume the full pools after
        a_own = jnp.concatenate([panel_a, zero_a])
        c = _accum(a_own, b_tiles, aux["pa0"], aux["pb0"], aux["ps0"]) \
            + _accum(a_pool, b_pool, aux["pa1"], aux["pb1"], aux["ps1"])
    else:
        c = _accum(a_pool, b_pool, aux["pa"], aux["pb"], aux["ps"])
    own = c[0]
    if packed:
        # row-packed reduce rounds: ship only the block-rows the sender's
        # items can touch; receivers scatter-add them home (a dummy target
        # row absorbs the padding).  This is outside any scan, so the
        # hot-loop jaxpr invariants are unaffected.
        nbr, bs = geom.a_nbr, geom.tm // geom.a_nbr
        c_rows = c.reshape(splan.n_out, nbr, bs, geom.tn)
        own_ext = jnp.concatenate(
            [c_rows[0],
             _pvary(jnp.zeros((1, bs, geom.tn), c.dtype), geom)])
        for axis, deltas in ((geom.axc, splan.row_deltas),
                             (geom.axr, splan.col_deltas)):
            pre = "r" if axis == geom.axc else "c"
            for delta in deltas:
                part = c_rows[aux[f"{pre}send{delta}"],
                              aux[f"{pre}row{delta}"]]
                part = lax.ppermute(part, axis, _steal3d_perm(g, delta))
                own_ext = own_ext.at[aux[f"{pre}tgt{delta}"]].add(part)
        return own_ext[:nbr].reshape(geom.tm, geom.tn).astype(geom.out_dtype)
    # reduce rounds: partial C tiles ride home to their owners; idle
    # senders point at the guaranteed-zero dummy slot
    for delta in splan.row_deltas:
        part = jnp.take(c, aux[f"rsend{delta}"], axis=0)
        own = own + lax.ppermute(part, geom.axc, _steal3d_perm(g, delta))
    for delta in splan.col_deltas:
        part = jnp.take(c, aux[f"csend{delta}"], axis=0)
        own = own + lax.ppermute(part, geom.axr, _steal3d_perm(g, delta))
    return own.astype(geom.out_dtype)
