"""Plan-based public API for the distributed sparse-matmul engine.

The paper's NVSHMEM implementation builds its algorithms on *persistent*
distributed-matrix objects (BCL ``DMatrix``) with a global pointer
directory: placement and skew are decided once, at construction, and every
multiply afterwards is pure communication + compute.  This module is the
TPU analogue of that design:

* :class:`DistBSR` / :class:`DistDense` — distributed-matrix *handles*
  wrapping a :class:`~repro.core.bsr.TiledBSR` / a grid-padded dense array.
  A handle carries the process-grid geometry, dtype, logical (uncropped)
  shape and — crucially — a cache of *placements* (natural / skew-rows /
  skew-cols / stationary-A), so the paper's ``k_offset`` skew is
  materialized at most once per operand and reused across calls.
* :func:`plan_matmul` -> :class:`MatmulPlan` — precomputes the static
  :class:`_Geom`, operand pack specs and placement requirements, and holds
  one jit-compiled ``shard_map`` executable: calling the plan again with the
  same abstract shapes never re-traces.  ``plan.cost_model()`` exposes the
  per-step network volume / flops that feed ``core/roofline.py`` and
  ``core/schedule.py``.
* :func:`matmul` — one polymorphic entry point dispatching
  sparse x dense -> SpMM, sparse x sparse -> SpGEMM and dense x dense ->
  the dense engine through :data:`REGISTRY` (an :class:`AlgorithmRegistry`).
  Algorithms register declaratively with their required operand placements,
  output unskew and per-step wire traffic, so new schedules (work-stealing
  layouts, stationary-B, ...) plug in without touching the engine.

The algorithm family (see the body docstrings): ``summa_bcast`` /
``summa_ag`` are the bulk-synchronous baselines, ``ring_c`` / ``ring_a``
the RDMA-style stationary-C / stationary-A rings with placement-time
``k_offset`` skew and prefetch via early ``ppermute``, ``ring_c_bidir`` a
bidirectional stationary-C ring that splits the output into column
half-panels circulating in opposite directions (full-duplex links), and
``steal3d`` the static realization of the paper's SS3.4 locality-aware
work stealing: a plan-time LPT assignment of the 3D (i, k, j) work grid
(:mod:`repro.core.steal3d`) executed as per-device pair lists with static
moved-tile and owner-reduction ppermute rounds.  ``plan_matmul(...,
algorithm="auto")`` scores every registered schedule with the
alpha-beta-gamma cost model (:func:`auto_select`) and builds the cheapest
— the static analogue of Bharadwaj et al.'s observation that the best
distributed sparse schedule flips with sparsity and aspect ratio.

SpGEMM additionally supports **sparse outputs** (``output="sparse"`` /
``"auto"``): a host-side symbolic phase (:mod:`repro.core.symbolic`,
re-exported here as :func:`symbolic_spgemm`) predicts C's block structure
from the operands' structures, allocates a capacity-bounded packed layout,
and the numeric phase (``ops.bsr_pair_accumulate``) scatter-accumulates
matched block products straight into it — no dense C tile, no B
densification, and the plan returns a :class:`DistBSR` so chained
multiplies ``matmul(matmul(A, A), A)`` stay packed end to end.  See
DESIGN.md "Symbolic/numeric SpGEMM".

Plans can additionally use the **packed wire format** (``wire="packed"``;
:mod:`repro.core.wire`): every sparse operand shipment — ring ppermutes,
SUMMA broadcasts/all-gathers, steal3d panel gathers, moved-tile rounds
and partial-C reductions, and the sparse-output pair traffic — carries
only *real* blocks at a bucketed wire capacity, with plan-time consume
maps (static gathers) reconstructing structure on the receiver.  Packed
plans are specialized to the operands' structure (fingerprints join the
cache key); ``wire="auto"`` packs the already-structure-keyed
sparse-output plans and keeps dense-output plans padded so bucketed
handles keep sharing cached executables.

Two hot-loop invariants the bodies maintain (asserted by the jaxpr test in
``tests/test_api.py``): sparse A tiles arrive *pre-augmented* from
:class:`~repro.core.bsr.TiledBSR` (no coverage concat+sort inside the
scanned step), and sparse B tiles never scatter inside the scan — padded
plans densify once per ring pass before the scan (``_densify_b``), packed
plans densify per step by a static *gather* (``ops.densify_packed``).

The legacy free functions in ``core/spmm.py`` remain as deprecated shims
delegating to the shared plan cache here.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import obs as _obs
from ..kernels import ops as kops
from ..kernels import ref as kref
from . import roofline as _roofline
from . import schedule as _schedule
from . import steal3d as _steal3d
from . import symbolic as _symbolic
from . import wire as _wire
from .bsr import TiledBSR
from .dist import (make_grid_mesh, place_b_for_stationary_a, skew_bsr,
                   skew_dense, unskew_c_rows)
from .grid import ProcessGrid, bucket_capacity, ceil_div, pad_to_multiple
from .symbolic import (SymbolicProduct, predicted_density,  # re-export
                       symbolic_spgemm)                     # (public)
from .wire import PackedOperand, wire_capacity              # re-export

__all__ = [
    "NATURAL", "SKEW_ROWS", "SKEW_COLS", "STATIONARY_A", "PLACEMENTS",
    "DistMatrix", "DistBSR", "DistDense",
    "Algorithm", "AlgorithmRegistry", "REGISTRY", "register_algorithm",
    "algorithms", "sparse_algorithms", "auto_select", "recommended_balance",
    "MatmulPlan", "plan_matmul", "matmul",
    "SymbolicProduct", "symbolic_spgemm", "predicted_density",
    "PackedOperand", "wire_capacity",
    "add_trace_hook", "remove_trace_hook",
    "clear_plan_cache", "plan_cache_size", "cache_stats",
    "invalidate_plans", "reshard",
    "validate_mesh",
]

# Placement states a DistMatrix can hold (the paper's directory remaps).
NATURAL = "natural"            # tile (i, j) at mesh position (i, j)
SKEW_ROWS = "skew_rows"        # position (i, j) holds tile (i, (i+j)%g)
SKEW_COLS = "skew_cols"        # position (i, j) holds tile ((i+j)%g, j)
STATIONARY_A = "stationary_a"  # position (i, j) holds tile (j, (i+j)%g)
PLACEMENTS = (NATURAL, SKEW_ROWS, SKEW_COLS, STATIONARY_A)


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


def _tree_ppermute(tree: Dict, axis: str, g: int, sign: int = 1) -> Dict:
    perm = [((d + sign) % g, d) for d in range(g)]
    return {k: lax.ppermute(v, axis, perm) for k, v in tree.items()}


def _tree_bcast(tree: Dict, axis: str, root, my_idx) -> Dict:
    sel = my_idx == root
    return {k: lax.psum(jnp.where(sel, v, jnp.zeros_like(v)), axis)
            for k, v in tree.items()}


def _pvary(x, geom: _Geom):
    return lax.pcast(x, (geom.axr, geom.axc), to="varying")


# ---------------------------------------------------------------------------
# Algorithm registry
# ---------------------------------------------------------------------------
class _LRUCache:
    """Small bounded cache: access-ordered, with an eviction counter.

    Plans, symbolic products and steal plans are all keyed (in part) on
    sparsity *structure*, so a long-running serving process that sees a
    stream of distinct structures would otherwise grow these caches — and
    the jitted executables / host index arrays they pin — without limit.
    Eviction is safe by construction: every entry is rebuilt on demand
    from its operands, so a cap only costs a rebuild on re-miss.
    ``evictions`` counts capacity evictions (not explicit invalidation)
    for observability; ``hits`` / ``misses`` count ``get`` outcomes so a
    serving layer can report plan reuse rates (hits/(hits+misses)) and
    plans-per-second without instrumenting every call site.  ``clear()``
    resets entries but keeps all counters.
    """

    def __init__(self, maxsize: int):
        self.maxsize = int(maxsize)
        self.evictions = 0
        self.hits = 0
        self.misses = 0
        self._d: "collections.OrderedDict" = collections.OrderedDict()

    def get(self, key, default=None):
        try:
            value = self._d[key]
        except KeyError:
            self.misses += 1
            return default
        self.hits += 1
        self._d.move_to_end(key)
        return value

    def __setitem__(self, key, value) -> None:
        if key in self._d:
            self._d.move_to_end(key)
        self._d[key] = value
        while len(self._d) > self.maxsize:
            self._d.popitem(last=False)
            self.evictions += 1

    def __delitem__(self, key) -> None:
        del self._d[key]

    def __contains__(self, key) -> bool:
        return key in self._d

    def __len__(self) -> int:
        return len(self._d)

    def __iter__(self):
        return iter(list(self._d))

    def clear(self) -> None:
        self._d.clear()

    def reset_counters(self) -> None:
        """Zero hit/miss/eviction counters (entries stay).  Lets a serving
        process window its plan-reuse rate without dropping hot plans."""
        self.evictions = 0
        self.hits = 0
        self.misses = 0


# Cache caps: small multiples of what a serving process legitimately keeps
# hot (a handful of operand structures x a few schedules/outputs each).
PLAN_CACHE_MAX = 128
SYMBOLIC_CACHE_MAX = 32
DENSITY_CACHE_MAX = 256
STEAL_CACHE_MAX = 32

# Shared plan cache (defined before the registry: registering over an
# existing algorithm name must evict that name's cached plans).
_PLAN_CACHE = _LRUCache(PLAN_CACHE_MAX)
# Symbolic-phase results, keyed on the operands' structure fingerprints
# (sparsity structure, not values): repeated sparse-output plans for the
# same structures skip the host-side pair-list construction.  Density-only
# results (the cheap prefix consulted by output="auto") cache separately so
# auto decisions that resolve to dense never build pair lists.
_SYMBOLIC_CACHE = _LRUCache(SYMBOLIC_CACHE_MAX)
_DENSITY_CACHE = _LRUCache(DENSITY_CACHE_MAX)
# steal3d assignments + pair lists, keyed on abstract shapes and (for
# sparse A) the structure fingerprint: repeated plans / auto_select scores
# for the same operands skip the host-side LPT + list construction.
_STEAL_CACHE = _LRUCache(STEAL_CACHE_MAX)


def clear_plan_cache() -> None:
    _PLAN_CACHE.clear()
    _SYMBOLIC_CACHE.clear()
    _DENSITY_CACHE.clear()
    _STEAL_CACHE.clear()


def plan_cache_size() -> int:
    return len(_PLAN_CACHE)


def cache_stats(reset: bool = False) -> Dict[str, Dict[str, int]]:
    """Sizes, caps, hit/miss and eviction counts of the plan-layer caches.

    ``reset=True`` zeroes the hit/miss/eviction counters *after* reading
    them (cache entries stay), so long-running serving processes can window
    plan-reuse rates without a process restart.  The returned dict always
    holds the pre-reset values.
    """
    caches = (("plans", _PLAN_CACHE), ("symbolic", _SYMBOLIC_CACHE),
              ("density", _DENSITY_CACHE), ("steal", _STEAL_CACHE))
    out = {name: {"size": len(c), "maxsize": c.maxsize,
                  "evictions": c.evictions,
                  "hits": c.hits, "misses": c.misses}
           for name, c in caches}
    if reset:
        for _, c in caches:
            c.reset_counters()
    return out


# The plan caches surface in obs snapshots as a pull-time callback: the
# registry reads cache_stats() lazily, so there is no per-hit instrument
# update and no duplicate counter state.
_obs.registry().register_callback("plan_caches", cache_stats)

# Machine preset scoring the *predicted* side of drift records (measured
# side is always the blocking wall clock).  Default matches the bench
# tables' predicted_s_v5e column; harnesses on other hardware override.
_DRIFT_MACHINE: Optional["_roofline.Machine"] = None


def set_drift_machine(machine) -> None:
    """Set the Machine used for the predicted side of obs drift records
    (``None`` restores the TPU_V5E default)."""
    global _DRIFT_MACHINE
    _DRIFT_MACHINE = machine


def _key_g(abstract_key) -> Optional[int]:
    """Grid size of a handle abstract key (None for unrecognized keys)."""
    if not isinstance(abstract_key, tuple) or not abstract_key:
        return None
    if abstract_key[0] == "bsr":
        return int(abstract_key[2][0])
    if abstract_key[0] == "dense":
        return int(abstract_key[2])
    return None


def invalidate_plans(*, algorithm: Optional[str] = None,
                     structure: Optional[str] = None,
                     g: Optional[int] = None) -> int:
    """Keyed plan-cache invalidation: evict only the entries matching every
    given filter (AND semantics; at least one filter is required).

    * ``algorithm`` — a registry name: entries whose schedule it is.
    * ``structure`` — a structure fingerprint (``DistBSR.structure_key()``):
      entries planned against that sparsity structure, including the
      symbolic/density/steal side caches keyed on fingerprints.
    * ``g`` — a grid size: entries planned for a g x g mesh (the filter a
      mesh-shrink recovery uses to drop every plan of the lost grid).

    This is the elastic replanner's eviction primitive: a drift-triggered
    re-fit drops only the algorithm whose cost model moved, a device-loss
    recovery drops only the dead grid's plans, and everything else stays
    hot.  Returns the number of entries evicted across all caches.
    """
    if algorithm is None and structure is None and g is None:
        raise ValueError(
            "invalidate_plans requires at least one of algorithm=, "
            "structure=, g= (use clear_plan_cache() to drop everything)")

    def plan_key_matches(k) -> bool:
        if algorithm is not None and k[0] != algorithm:
            return False
        if g is not None and _key_g(k[7]) != g and _key_g(k[8]) != g:
            return False
        if structure is not None and structure not in k[9:]:
            return False
        return True

    evicted = 0
    for key in [k for k in _PLAN_CACHE if plan_key_matches(k)]:
        del _PLAN_CACHE[key]
        evicted += 1
    # Side caches are keyed on fingerprints/abstract shapes, not algorithm:
    # sweep them only for structure / grid filters.
    if structure is not None or g is not None:
        for key in [k for k in _STEAL_CACHE
                    if (structure is None or structure == k[2])
                    and (g is None or _key_g(k[0]) == g)]:
            del _STEAL_CACHE[key]
            evicted += 1
        if algorithm is None and structure is not None:
            for cache in (_SYMBOLIC_CACHE, _DENSITY_CACHE):
                for key in [k for k in cache if structure in k]:
                    del cache[key]
                    evicted += 1
    return evicted


def _evict_plans_for_algorithm(name: str) -> None:
    invalidate_plans(algorithm=name)


@dataclasses.dataclass(frozen=True)
class Algorithm:
    """A registered schedule: shard_map body + declarative placement needs.

    ``a_placement`` / ``b_placement`` name the :data:`PLACEMENTS` state each
    operand must be in before the body runs (the handle caches the
    transform); ``unskew_out`` names the inverse placement applied to the
    output; ``wire`` lists which tiles ride the network each inner step
    (repeats allowed — ``ring_c_bidir`` ships A in both directions; feeds
    :meth:`MatmulPlan.cost_model`); ``wire_amortized`` marks schedules whose
    communication happens once up front (all-gather) rather than per step;
    ``duplex=2`` marks schedules that split traffic over both directions of
    the full-duplex links, halving serialized wire time.
    """
    name: str
    body: Callable
    a_placement: str = NATURAL
    b_placement: str = NATURAL
    unskew_out: Optional[str] = None        # None | "rows"
    wire: Tuple[str, ...] = ("a", "b")      # tile names from {"a", "b", "c"}
    wire_amortized: bool = False
    style: str = "rdma"                     # "rdma" | "bsp"
    duplex: int = 1                         # link directions used per step
    msgs_per_step: Optional[int] = None     # alpha-term count; len(wire) if
                                            # None (bidir splits B: 4 msgs)
    sparse_body: Optional[Callable] = None  # packed-output SpGEMM body
    k_order: Optional[Callable] = None      # (i, j, t, g) -> inner index k
                                            # of step t on device (i, j);
                                            # schedules the symbolic phase's
                                            # pair lists (sparse_body only)
    balance_axis: str = "rows"              # operand balance this schedule
                                            # benefits from (planner hint)
    static_planner: Optional[Callable] = None
                                            # (a_h, b_h, geom, wire) ->
                                            # StealPlan: plan-time builder
                                            # of a static work-grid
                                            # dispatch; the body then runs
                                            # as body(a, b, aux, geom,
                                            # steal_plan)
    cost_fn: Optional[Callable] = None      # (alg, geom, a_h, b_h, wire)
                                            # -> cost dict, replacing the
                                            # generic _cost_model for
                                            # schedules whose cost is
                                            # structure-dependent (steal3d)
    packed_body: Optional[Callable] = None  # packed-wire dense-output body
                                            # body(a, b, aux, geom); aux is
                                            # the wire_planner's array dict
    packable: Tuple[str, ...] = ()          # operands this schedule can
                                            # ship packed ("a"/"b"); the
                                            # sparse-output path packs both
                                            # operands for every schedule
    wire_planner: Optional[Callable] = None
                                            # (a_po, b_po, geom) -> aux
                                            # dict of [g, g, ...] arrays
                                            # (consume maps for the packed
                                            # body; None po => operand not
                                            # packed on this plan)


class AlgorithmRegistry:
    """Name -> :class:`Algorithm` map driving :func:`matmul` dispatch."""

    def __init__(self):
        self._algorithms: Dict[str, Algorithm] = {}

    def register(self, alg: Algorithm, *, overwrite: bool = False) -> Algorithm:
        for placement, who in ((alg.a_placement, "a"), (alg.b_placement, "b")):
            if placement not in PLACEMENTS:
                raise ValueError(
                    f"algorithm {alg.name!r}: unknown {who}_placement "
                    f"{placement!r}; one of {PLACEMENTS}")
        if alg.name in self._algorithms:
            if not overwrite:
                raise ValueError(f"algorithm {alg.name!r} already registered")
            _evict_plans_for_algorithm(alg.name)
        self._algorithms[alg.name] = alg
        return alg

    def unregister(self, name: str) -> None:
        if self._algorithms.pop(name, None) is not None:
            _evict_plans_for_algorithm(name)

    def get(self, name: str) -> Algorithm:
        try:
            return self._algorithms[name]
        except KeyError:
            raise ValueError(
                f"unknown algorithm {name!r}; one of {self.names()}") from None

    def names(self) -> Tuple[str, ...]:
        return tuple(self._algorithms)

    def __contains__(self, name: str) -> bool:
        return name in self._algorithms

    def __iter__(self):
        return iter(self._algorithms.values())

    def __len__(self) -> int:
        return len(self._algorithms)


REGISTRY = AlgorithmRegistry()


def register_algorithm(name: str, *, a_placement: str = NATURAL,
                       b_placement: str = NATURAL,
                       unskew_out: Optional[str] = None,
                       wire: Tuple[str, ...] = ("a", "b"),
                       wire_amortized: bool = False, style: str = "rdma",
                       duplex: int = 1, msgs_per_step: Optional[int] = None,
                       sparse_body: Optional[Callable] = None,
                       k_order: Optional[Callable] = None,
                       balance_axis: str = "rows",
                       static_planner: Optional[Callable] = None,
                       cost_fn: Optional[Callable] = None,
                       packed_body: Optional[Callable] = None,
                       packable: Tuple[str, ...] = (),
                       wire_planner: Optional[Callable] = None,
                       registry: AlgorithmRegistry = REGISTRY):
    """Decorator registering a shard_map body as a named algorithm."""
    def deco(body):
        registry.register(Algorithm(
            name=name, body=body, a_placement=a_placement,
            b_placement=b_placement, unskew_out=unskew_out, wire=wire,
            wire_amortized=wire_amortized, style=style, duplex=duplex,
            msgs_per_step=msgs_per_step, sparse_body=sparse_body,
            k_order=k_order, balance_axis=balance_axis,
            static_planner=static_planner, cost_fn=cost_fn,
            packed_body=packed_body, packable=packable,
            wire_planner=wire_planner))
        return body
    return deco


def algorithms() -> Tuple[str, ...]:
    """Names of all registered algorithms (registration order)."""
    return REGISTRY.names()


def sparse_algorithms() -> Tuple[str, ...]:
    """Names of algorithms with a sparse-output (packed SpGEMM) body."""
    return tuple(a.name for a in REGISTRY if a.sparse_body is not None)


def recommended_balance(algorithm: str) -> str:
    """The operand balance axis the named schedule benefits from.

    Stationary-C schedules are dominated by the A tiles streamed each step,
    so spreading nonzero blocks over grid *rows* shrinks their capacity;
    the stationary-A ring's cost is dominated by B/C traffic and its output
    rides a reverse ring, so a *column* balance (compensated on the B side,
    leaving C unpermuted) composes better.  Feed the result to
    ``DistBSR.from_dense(balance=...)``.
    """
    return REGISTRY.get(algorithm).balance_axis


# ---------------------------------------------------------------------------
# Sparse-output bodies (packed SpGEMM; see plan_matmul(output="sparse"))
# ---------------------------------------------------------------------------
# The numeric phase of symbolic/numeric SpGEMM: both operands stay in their
# stored block form (only ``blocks`` rides the wire — the pair lists encode
# all structure, so rows/cols never leave the host), and each step
# scatter-accumulates matched block products into the packed output slots
# allocated by the symbolic phase.  No dense C tile, and no B densification,
# ever materializes on a device.
def _sparse_step(a_t: Dict, b_t: Dict, pa, pb, ps, geom: _Geom):
    return kops.bsr_pair_accumulate(
        a_t["blocks"], b_t["blocks"], pa, pb, ps, n_slots=geom.c_store,
        out_dtype=jnp.float32, impl=geom.impl)


def _sparse_c0(a: Dict, geom: _Geom):
    bs = a["blocks"].shape[-1]
    return _pvary(jnp.zeros((geom.c_store, bs, bs), jnp.float32), geom)


def _sparse_body_summa_bcast(a, b, pairs, geom: _Geom):
    """Bulk-synchronous SUMMA with packed sparse output."""
    my_row = lax.axis_index(geom.axr)
    my_col = lax.axis_index(geom.axc)
    if geom.overlap:
        # split-step (see _body_summa_bcast): step t carries inner step
        # t's panels and pairs while broadcasting step t+1's panels.
        a_c = _tree_bcast(a, geom.axc, jnp.int32(0), my_col)
        b_c = _tree_bcast(b, geom.axr, jnp.int32(0), my_row)

        def step(carry, xs):
            a_k, b_k, c = carry
            k, pa, pb, ps = xs
            a_n = _tree_bcast(a, geom.axc, k, my_col)
            b_n = _tree_bcast(b, geom.axr, k, my_row)
            c = c + _sparse_step(a_k, b_k, pa, pb, ps, geom)
            return (a_n, b_n, c), None

        (a_l, b_l, c), _ = lax.scan(
            step, (a_c, b_c, _sparse_c0(a, geom)),
            (jnp.arange(1, geom.g), pairs["pa"][:-1], pairs["pb"][:-1],
             pairs["ps"][:-1]))
        c = c + _sparse_step(a_l, b_l, pairs["pa"][-1], pairs["pb"][-1],
                             pairs["ps"][-1], geom)
        return c.astype(geom.out_dtype)

    def step(c, xs):
        k, pa, pb, ps = xs
        a_k = _tree_bcast(a, geom.axc, k, my_col)
        b_k = _tree_bcast(b, geom.axr, k, my_row)
        return c + _sparse_step(a_k, b_k, pa, pb, ps, geom), None

    c, _ = lax.scan(step, _sparse_c0(a, geom),
                    (jnp.arange(geom.g), pairs["pa"], pairs["pb"],
                     pairs["ps"]))
    return c.astype(geom.out_dtype)


def _sparse_body_summa_ag(a, b, pairs, geom: _Geom):
    """All-gather SUMMA with packed sparse output."""
    a_g = {k: lax.all_gather(v, geom.axc) for k, v in a.items()}
    b_g = {k: lax.all_gather(v, geom.axr) for k, v in b.items()}

    def step(c, xs):
        k, pa, pb, ps = xs
        a_k = {kk: v[k] for kk, v in a_g.items()}
        b_k = {kk: v[k] for kk, v in b_g.items()}
        return c + _sparse_step(a_k, b_k, pa, pb, ps, geom), None

    c, _ = lax.scan(step, _sparse_c0(a, geom),
                    (jnp.arange(geom.g), pairs["pa"], pairs["pb"],
                     pairs["ps"]))
    return c.astype(geom.out_dtype)


def _sparse_body_ring_c(a, b, pairs, geom: _Geom):
    """Stationary-C ring with packed sparse output.

    Same skewed placement and prefetch structure as ``ring_c``; B rides the
    ring in stored block form (its densified tile never exists), and the
    scanned step consumes the step-scheduled pair lists as scan inputs.
    """
    if geom.overlap:
        # two-slot double buffer (see _body_ring_c); scan input t pairs
        # with the tile of generation t, so the xs are sliced to g-1 and
        # the last pair list feeds the epilogue accumulate.
        a_f = _tree_ppermute(a, geom.axc, geom.g)
        b_f = _tree_ppermute(b, geom.axr, geom.g)

        def step(carry, xs):
            a_t, b_t, a_f, b_f, c = carry
            pa, pb, ps = xs
            a_n = _tree_ppermute(a_f, geom.axc, geom.g)
            b_n = _tree_ppermute(b_f, geom.axr, geom.g)
            c = c + _sparse_step(a_t, b_t, pa, pb, ps, geom)
            return (a_f, b_f, a_n, b_n, c), None

        (a_l, b_l, _, _, c), _ = lax.scan(
            step, (a, b, a_f, b_f, _sparse_c0(a, geom)),
            (pairs["pa"][:-1], pairs["pb"][:-1], pairs["ps"][:-1]))
        c = c + _sparse_step(a_l, b_l, pairs["pa"][-1], pairs["pb"][-1],
                             pairs["ps"][-1], geom)
        return c.astype(geom.out_dtype)

    def step(carry, xs):
        a_t, b_t, c = carry
        pa, pb, ps = xs
        a_n = _tree_ppermute(a_t, geom.axc, geom.g)   # prefetch (paper SS3.3)
        b_n = _tree_ppermute(b_t, geom.axr, geom.g)
        c = c + _sparse_step(a_t, b_t, pa, pb, ps, geom)
        return (a_n, b_n, c), None

    (_, _, c), _ = lax.scan(step, (a, b, _sparse_c0(a, geom)),
                            (pairs["pa"], pairs["pb"], pairs["ps"]))
    return c.astype(geom.out_dtype)


# ---------------------------------------------------------------------------
# Packed-wire dense-output bodies (plan_matmul(wire="packed"))
# ---------------------------------------------------------------------------
# The packed variants ship ONLY real blocks: a sparse A tile rides as a
# packed [wire_capacity, bs, bs] buffer (no coverage blocks, no rows/cols
# index traffic) and a sparse B tile likewise, densified on the consumer
# by a static *gather* (ops.densify_packed) instead of the pre-scan
# scatter.  All structure lives in plan-time consume maps (repro.core.wire)
# riding as scan inputs — per-device local data, never on the network —
# so the scanned steps stay sort/scatter-free (the jaxpr invariant).
def _ring_perm(g: int, sign: int = 1):
    return [((d + sign) % g, d) for d in range(g)]


def _packed_a_mm(a_blocks, gidx, rows, cols, b_dense, geom: _Geom):
    """One packed local SpMM step: gather the coverage-augmented block
    list out of the packed buffer, then the standard augment-free kernel."""
    return kops.bsr_spmm_raw(a_blocks[gidx], rows, cols, b_dense,
                             n_block_rows=geom.a_nbr, impl=geom.impl,
                             augment=False).astype(geom.out_dtype)


def _packed_b_dense(b_buf, dmap, geom: _Geom):
    return kops.densify_packed(b_buf, dmap, n_block_rows=geom.b_nbr,
                               n_block_cols=geom.b_nbc)


def _packed_body_ring_c(a, b, aux, geom: _Geom):
    """Stationary-C ring over packed wire buffers (paper Alg 2)."""
    b_packed = "b_dmap" in aux
    b0 = b["blocks"] if b_packed else _densify_b(b, geom)["dense"]
    xs = {"ag": aux["a_gidx"], "ar": aux["a_rows"], "ac": aux["a_cols"]}
    if b_packed:
        xs["bd"] = aux["b_dmap"]
    c0 = _pvary(jnp.zeros((geom.tm, geom.tn), dtype=geom.out_dtype), geom)
    if geom.overlap:
        # two-slot double buffer (see _body_ring_c); consume maps for
        # step t pair with tile generation t, so xs slice to g-1 and the
        # final maps feed the epilogue accumulate.
        last = {k: v[-1] for k, v in xs.items()}
        xs = {k: v[:-1] for k, v in xs.items()}
        a_f = lax.ppermute(a["blocks"], geom.axc, _ring_perm(geom.g))
        b_f = lax.ppermute(b0, geom.axr, _ring_perm(geom.g))

        def step(carry, xs):
            a_blk, b_buf, a_f, b_f, c = carry
            a_n = lax.ppermute(a_f, geom.axc, _ring_perm(geom.g))
            b_n = lax.ppermute(b_f, geom.axr, _ring_perm(geom.g))
            b_dense = _packed_b_dense(b_buf, xs["bd"], geom) if b_packed \
                else b_buf
            c = c + _packed_a_mm(a_blk, xs["ag"], xs["ar"], xs["ac"],
                                 b_dense, geom)
            return (a_f, b_f, a_n, b_n, c), None

        (a_l, b_l, _, _, c), _ = lax.scan(
            step, (a["blocks"], b0, a_f, b_f, c0), xs)
        b_dense = _packed_b_dense(b_l, last["bd"], geom) if b_packed else b_l
        return c + _packed_a_mm(a_l, last["ag"], last["ar"], last["ac"],
                                b_dense, geom)

    def step(carry, xs):
        a_blk, b_buf, c = carry
        a_n = lax.ppermute(a_blk, geom.axc, _ring_perm(geom.g))  # prefetch
        b_n = lax.ppermute(b_buf, geom.axr, _ring_perm(geom.g))
        b_dense = _packed_b_dense(b_buf, xs["bd"], geom) if b_packed \
            else b_buf
        c = c + _packed_a_mm(a_blk, xs["ag"], xs["ar"], xs["ac"], b_dense,
                             geom)
        return (a_n, b_n, c), None

    (_, _, c), _ = lax.scan(step, (a["blocks"], b0, c0), xs)
    return c


def _packed_body_ring_c_bidir(a, b, aux, geom: _Geom):
    """Bidirectional stationary-C ring, A packed in both directions.

    B's column half-panels are not block-aligned (tn // 2 need not be a
    block multiple), so B rides densified as in the padded body; only the
    A streams — the bidir schedule's doubled wire term — pack.
    """
    b = _densify_b(b, geom)
    half = geom.tn // 2
    b_fwd, b_bwd = b["dense"][:, :half], b["dense"][:, half:]
    xs = {"fg": aux["a_gidx"], "fr": aux["a_rows"], "fc": aux["a_cols"],
          "bg": aux["a_gidx_bwd"], "br": aux["a_rows_bwd"],
          "bc": aux["a_cols_bwd"]}
    c_l0 = _pvary(jnp.zeros((geom.tm, half), dtype=geom.out_dtype), geom)
    c_r0 = _pvary(jnp.zeros((geom.tm, geom.tn - half),
                            dtype=geom.out_dtype), geom)
    if geom.overlap:
        # four streams x two slots (see _body_ring_c_bidir), consume maps
        # sliced so step t's maps meet tile generation t
        last = {k: v[-1] for k, v in xs.items()}
        xs = {k: v[:-1] for k, v in xs.items()}
        a_ff = lax.ppermute(a["blocks"], geom.axc, _ring_perm(geom.g, +1))
        a_bf = lax.ppermute(a["blocks"], geom.axc, _ring_perm(geom.g, -1))
        b_ff = lax.ppermute(b_fwd, geom.axr, _ring_perm(geom.g, +1))
        b_bf = lax.ppermute(b_bwd, geom.axr, _ring_perm(geom.g, -1))

        def step(carry, xs):
            a_f, a_b, b_f, b_b, a_ff, a_bf, b_ff, b_bf, c_l, c_r = carry
            a_fn = lax.ppermute(a_ff, geom.axc, _ring_perm(geom.g, +1))
            a_bn = lax.ppermute(a_bf, geom.axc, _ring_perm(geom.g, -1))
            b_fn = lax.ppermute(b_ff, geom.axr, _ring_perm(geom.g, +1))
            b_bn = lax.ppermute(b_bf, geom.axr, _ring_perm(geom.g, -1))
            c_l = c_l + _packed_a_mm(a_f, xs["fg"], xs["fr"], xs["fc"],
                                     b_f, geom)
            c_r = c_r + _packed_a_mm(a_b, xs["bg"], xs["br"], xs["bc"],
                                     b_b, geom)
            return (a_ff, a_bf, b_ff, b_bf, a_fn, a_bn, b_fn, b_bn,
                    c_l, c_r), None

        (a_fl, a_bl, b_fl, b_bl, _, _, _, _, c_l, c_r), _ = lax.scan(
            step, (a["blocks"], a["blocks"], b_fwd, b_bwd,
                   a_ff, a_bf, b_ff, b_bf, c_l0, c_r0), xs)
        c_l = c_l + _packed_a_mm(a_fl, last["fg"], last["fr"], last["fc"],
                                 b_fl, geom)
        c_r = c_r + _packed_a_mm(a_bl, last["bg"], last["br"], last["bc"],
                                 b_bl, geom)
        return jnp.concatenate([c_l, c_r], axis=1)

    def step(carry, xs):
        a_f, a_b, b_f, b_b, c_l, c_r = carry
        a_fn = lax.ppermute(a_f, geom.axc, _ring_perm(geom.g, +1))
        a_bn = lax.ppermute(a_b, geom.axc, _ring_perm(geom.g, -1))
        b_fn = lax.ppermute(b_f, geom.axr, _ring_perm(geom.g, +1))
        b_bn = lax.ppermute(b_b, geom.axr, _ring_perm(geom.g, -1))
        c_l = c_l + _packed_a_mm(a_f, xs["fg"], xs["fr"], xs["fc"], b_f,
                                 geom)
        c_r = c_r + _packed_a_mm(a_b, xs["bg"], xs["br"], xs["bc"], b_b,
                                 geom)
        return (a_fn, a_bn, b_fn, b_bn, c_l, c_r), None

    (_, _, _, _, c_l, c_r), _ = lax.scan(
        step, (a["blocks"], a["blocks"], b_fwd, b_bwd, c_l0, c_r0), xs)
    return jnp.concatenate([c_l, c_r], axis=1)


def _packed_body_ring_a(a, b, aux, geom: _Geom):
    """Stationary-A ring with the sparse B operand packed on the wire.

    A never moves (nothing to pack); the win is B riding as real blocks
    instead of a densified tile, gather-densified each step.  Partial C
    tiles still ride back dense — their structure differs per hop (the
    ROADMAP's sparse-output ring_a item).
    """
    acc0 = _pvary(jnp.zeros((geom.tm, geom.tn), dtype=geom.out_dtype), geom)
    if geom.overlap:
        # B stream two-slot only — the accumulator ring is a serial
        # dependence chain and cannot be double-buffered (see _body_ring_a)
        b_f = lax.ppermute(b["blocks"], geom.axr, _ring_perm(geom.g))

        def step(carry, bd):
            b_blk, b_f, acc = carry
            b_n = lax.ppermute(b_f, geom.axr, _ring_perm(geom.g))
            acc = acc + _local_mm(
                a, {"dense": _packed_b_dense(b_blk, bd, geom)}, geom)
            acc = lax.ppermute(acc, geom.axc, _ring_perm(geom.g))
            return (b_f, b_n, acc), None

        (b_l, _, acc), _ = lax.scan(step, (b["blocks"], b_f, acc0),
                                    aux["b_dmap"][:-1])
        acc = acc + _local_mm(
            a, {"dense": _packed_b_dense(b_l, aux["b_dmap"][-1], geom)},
            geom)
        return lax.ppermute(acc, geom.axc, _ring_perm(geom.g))

    def step(carry, bd):
        b_blk, acc = carry
        b_n = lax.ppermute(b_blk, geom.axr, _ring_perm(geom.g))  # prefetch
        acc = acc + _local_mm(a, {"dense": _packed_b_dense(b_blk, bd, geom)},
                              geom)
        acc = lax.ppermute(acc, geom.axc, _ring_perm(geom.g))
        return (b_n, acc), None

    (_, acc), _ = lax.scan(step, (b["blocks"], acc0), aux["b_dmap"])
    return acc


def _packed_body_summa_ag(a, b, aux, geom: _Geom):
    """All-gather SUMMA over packed panels (per-source packed segments)."""
    b_packed = "b_dmap" in aux
    a_pool = lax.all_gather(a["blocks"], geom.axc)   # [g, wc_a, bs, bs]
    a_flat = a_pool.reshape((-1,) + a_pool.shape[-2:])
    xs = {"ag": aux["a_gidx"], "ar": aux["a_rows"], "ac": aux["a_cols"]}
    if b_packed:
        b_pool = lax.all_gather(b["blocks"], geom.axr)
        b_flat = b_pool.reshape((-1,) + b_pool.shape[-2:])
        xs["bd"] = aux["b_dmap"]
    else:
        b_g = lax.all_gather(_densify_b(b, geom)["dense"], geom.axr)
        xs["k"] = jnp.arange(geom.g)

    def step(c, xs):
        b_dense = _packed_b_dense(b_flat, xs["bd"], geom) if b_packed \
            else b_g[xs["k"]]
        c = c + _packed_a_mm(a_flat, xs["ag"], xs["ar"], xs["ac"], b_dense,
                             geom)
        return c, None

    c0 = _pvary(jnp.zeros((geom.tm, geom.tn), dtype=geom.out_dtype), geom)
    c, _ = lax.scan(step, c0, xs)
    return c


def _packed_body_summa_bcast(a, b, aux, geom: _Geom):
    """Bulk-synchronous SUMMA broadcasting packed buffers per inner step."""
    b_packed = "b_dmap" in aux
    my_row = lax.axis_index(geom.axr)
    my_col = lax.axis_index(geom.axc)
    b0 = b["blocks"] if b_packed else _densify_b(b, geom)["dense"]
    xs = {"ag": aux["a_gidx"], "ar": aux["a_rows"], "ac": aux["a_cols"],
          "k": jnp.arange(geom.g)}
    if b_packed:
        xs["bd"] = aux["b_dmap"]

    def bcast(k):
        a_k = lax.psum(jnp.where(my_col == k, a["blocks"],
                                 jnp.zeros_like(a["blocks"])), geom.axc)
        b_k = lax.psum(jnp.where(my_row == k, b0, jnp.zeros_like(b0)),
                       geom.axr)
        return a_k, b_k

    c0 = _pvary(jnp.zeros((geom.tm, geom.tn), dtype=geom.out_dtype), geom)
    if geom.overlap:
        # split-step (see _body_summa_bcast): broadcast inner step k while
        # accumulating the carried panels of step k-1
        last = {k: v[-1] for k, v in xs.items()}
        xs = {k: v[1:] if k == "k" else v[:-1] for k, v in xs.items()}
        a_c, b_c = bcast(jnp.int32(0))

        def step(carry, xs):
            a_k, b_k, c = carry
            a_n, b_n = bcast(xs["k"])
            b_dense = _packed_b_dense(b_k, xs["bd"], geom) if b_packed \
                else b_k
            c = c + _packed_a_mm(a_k, xs["ag"], xs["ar"], xs["ac"],
                                 b_dense, geom)
            return (a_n, b_n, c), None

        (a_l, b_l, c), _ = lax.scan(step, (a_c, b_c, c0), xs)
        b_dense = _packed_b_dense(b_l, last["bd"], geom) if b_packed else b_l
        return c + _packed_a_mm(a_l, last["ag"], last["ar"], last["ac"],
                                b_dense, geom)

    def step(c, xs):
        a_k, b_k = bcast(xs["k"])
        b_dense = _packed_b_dense(b_k, xs["bd"], geom) if b_packed else b_k
        c = c + _packed_a_mm(a_k, xs["ag"], xs["ar"], xs["ac"], b_dense,
                             geom)
        return c, None

    c, _ = lax.scan(step, c0, xs)
    return c


# ---- per-schedule wire planners (consume-map construction) ----------------
def _wire_consume(aux, prefix, po, tiles, bases=None):
    cons = _wire.schedule_consume(po, tiles, bases)
    aux[f"{prefix}_gidx"] = cons["gidx"]
    aux[f"{prefix}_rows"] = cons["rows"]
    aux[f"{prefix}_cols"] = cons["cols"]


def _wire_planner_ring_c(a_po, b_po, geom: _Geom):
    aux: Dict[str, np.ndarray] = {}
    if a_po is not None:
        _wire_consume(aux, "a", a_po, _wire.tiles_ring_c(geom.g))
    if b_po is not None:
        aux["b_dmap"] = _wire.schedule_dense_map(
            b_po, _wire.tiles_ring_c_b(geom.g))
    return aux


def _wire_planner_ring_c_bidir(a_po, b_po, geom: _Geom):
    aux: Dict[str, np.ndarray] = {}
    _wire_consume(aux, "a", a_po, _wire.tiles_ring_c(geom.g))
    cons = _wire.schedule_consume(a_po, _wire.tiles_ring_c_bwd(geom.g))
    aux["a_gidx_bwd"] = cons["gidx"]
    aux["a_rows_bwd"] = cons["rows"]
    aux["a_cols_bwd"] = cons["cols"]
    return aux


def _wire_planner_ring_a(a_po, b_po, geom: _Geom):
    return {"b_dmap": _wire.schedule_dense_map(
        b_po, _wire.tiles_ring_a_b(geom.g))}


def _summa_bases(g: int, wc: int) -> np.ndarray:
    """Flat base offset of inner step k's tile in an all-gathered pool."""
    return np.broadcast_to(np.arange(g, dtype=np.int64) * wc, (g, g, g))


def _wire_planner_summa_ag(a_po, b_po, geom: _Geom):
    g = geom.g
    aux: Dict[str, np.ndarray] = {}
    if a_po is not None:
        _wire_consume(aux, "a", a_po, _wire.tiles_summa_a(g),
                      _summa_bases(g, a_po.wire_capacity))
    if b_po is not None:
        aux["b_dmap"] = _wire.schedule_dense_map(
            b_po, _wire.tiles_summa_b(g),
            _summa_bases(g, b_po.wire_capacity))
    return aux


def _wire_planner_summa_bcast(a_po, b_po, geom: _Geom):
    g = geom.g
    aux: Dict[str, np.ndarray] = {}
    if a_po is not None:
        _wire_consume(aux, "a", a_po, _wire.tiles_summa_a(g))
    if b_po is not None:
        aux["b_dmap"] = _wire.schedule_dense_map(b_po,
                                                 _wire.tiles_summa_b(g))
    return aux


# ---------------------------------------------------------------------------
# Algorithm bodies (run inside shard_map on local tile views)
# ---------------------------------------------------------------------------
@register_algorithm("summa_bcast", style="bsp",
                    sparse_body=_sparse_body_summa_bcast,
                    packed_body=_packed_body_summa_bcast,
                    packable=("a", "b"),
                    wire_planner=_wire_planner_summa_bcast,
                    k_order=lambda i, j, t, g: t + 0 * (i + j))
def _body_summa_bcast(a, b, geom: _Geom):
    """Bulk-synchronous SUMMA (paper SS2.2): a broadcast per inner step."""
    b = _densify_b(b, geom)
    my_row = lax.axis_index(geom.axr)
    my_col = lax.axis_index(geom.axc)
    c0 = _pvary(jnp.zeros((geom.tm, geom.tn), dtype=geom.out_dtype), geom)
    if geom.overlap:
        # split-step: broadcast inner step k+1 before accumulating step k's
        # carried panels, so the collective overlaps the local matmul
        a_c = _tree_bcast(a, geom.axc, jnp.int32(0), my_col)
        b_c = _tree_bcast(b, geom.axr, jnp.int32(0), my_row)

        def step(carry, k):
            a_k, b_k, c = carry
            a_n = _tree_bcast(a, geom.axc, k, my_col)
            b_n = _tree_bcast(b, geom.axr, k, my_row)
            c = c + _local_mm(a_k, b_k, geom)
            return (a_n, b_n, c), None

        (a_l, b_l, c), _ = lax.scan(step, (a_c, b_c, c0),
                                    jnp.arange(1, geom.g))
        return c + _local_mm(a_l, b_l, geom)

    def step(c, k):
        a_k = _tree_bcast(a, geom.axc, k, my_col)  # bcast A[:, k] along rows
        b_k = _tree_bcast(b, geom.axr, k, my_row)  # bcast B[k, :] along cols
        return c + _local_mm(a_k, b_k, geom), None

    c, _ = lax.scan(step, c0, jnp.arange(geom.g))
    return c


@register_algorithm("summa_ag", style="bsp", wire_amortized=True,
                    sparse_body=_sparse_body_summa_ag,
                    packed_body=_packed_body_summa_ag,
                    packable=("a", "b"),
                    wire_planner=_wire_planner_summa_ag,
                    k_order=lambda i, j, t, g: t + 0 * (i + j))
def _body_summa_ag(a, b, geom: _Geom):
    """All-gather SUMMA: one big up-front collective, g x tile footprint.

    No overlap variant: the schedule is wire-amortized — every inner step
    depends on the single up-front all-gather, so there is no per-step
    transfer to double-buffer (the gather gates all compute).
    """
    b = _densify_b(b, geom)
    a_g = {k: lax.all_gather(v, geom.axc) for k, v in a.items()}
    b_g = {k: lax.all_gather(v, geom.axr) for k, v in b.items()}

    def step(c, k):
        a_k = {kk: v[k] for kk, v in a_g.items()}
        b_k = {kk: v[k] for kk, v in b_g.items()}
        return c + _local_mm(a_k, b_k, geom), None

    c0 = _pvary(jnp.zeros((geom.tm, geom.tn), dtype=geom.out_dtype), geom)
    c, _ = lax.scan(step, c0, jnp.arange(geom.g))
    return c


@register_algorithm("ring_c", a_placement=SKEW_ROWS, b_placement=SKEW_COLS,
                    sparse_body=_sparse_body_ring_c,
                    packed_body=_packed_body_ring_c,
                    packable=("a", "b"),
                    wire_planner=_wire_planner_ring_c,
                    k_order=lambda i, j, t, g: (i + j + t) % g)
def _body_ring_c(a, b, geom: _Geom):
    """Paper Alg 2 (stationary-C): skewed placement + neighbour ppermute."""
    b = _densify_b(b, geom)
    c0 = _pvary(jnp.zeros((geom.tm, geom.tn), dtype=geom.out_dtype), geom)
    if geom.overlap:
        # Split-step double buffer: the carry holds the tile being
        # consumed AND the tile in flight, so the transfer consumed at
        # step t+1 was issued at step t-1 — a full local matmul of slack
        # for the collective-permute DMA.  The prologue issues step 1's
        # transfer, the scan runs g-1 steps, and the epilogue accumulates
        # the last tile with nothing left to prefetch: g permutes per
        # stream total, exactly the bulk body's wire traffic.
        a_f = _tree_ppermute(a, geom.axc, geom.g)
        b_f = _tree_ppermute(b, geom.axr, geom.g)

        def step(carry, _):
            a_t, b_t, a_f, b_f, c = carry
            a_n = _tree_ppermute(a_f, geom.axc, geom.g)   # step t+2's tile
            b_n = _tree_ppermute(b_f, geom.axr, geom.g)
            c = c + _local_mm(a_t, b_t, geom)
            return (a_f, b_f, a_n, b_n, c), None

        (a_l, b_l, _, _, c), _ = lax.scan(step, (a, b, a_f, b_f, c0), None,
                                          length=geom.g - 1)
        return c + _local_mm(a_l, b_l, geom)

    def step(carry, _):
        a_t, b_t, c = carry
        # "async_get_tile" for step k+1, issued before the local matmul so
        # the collective-permute DMA overlaps MXU work (paper SS3.3 prefetch).
        a_n = _tree_ppermute(a_t, geom.axc, geom.g)
        b_n = _tree_ppermute(b_t, geom.axr, geom.g)
        c = c + _local_mm(a_t, b_t, geom)
        return (a_n, b_n, c), None

    (_, _, c), _ = lax.scan(step, (a, b, c0), None, length=geom.g)
    return c


@register_algorithm("ring_a", b_placement=STATIONARY_A, unskew_out="rows",
                    wire=("b", "c"), balance_axis="cols",
                    packed_body=_packed_body_ring_a, packable=("b",),
                    wire_planner=_wire_planner_ring_a)
def _body_ring_a(a, b, geom: _Geom):
    """Paper Alg 1 (stationary-A): B rides the ring, partial C rides back."""
    b = _densify_b(b, geom)
    acc0 = _pvary(jnp.zeros((geom.tm, geom.tn), dtype=geom.out_dtype), geom)
    if geom.overlap:
        # Only the B stream double-buffers: the partial-C permute depends
        # on the accumulate it follows (the ride-home chain is inherently
        # serial), so C's hop count stays g while B's transfers gain a
        # full matmul of slack.
        b_f = _tree_ppermute(b, geom.axr, geom.g)

        def step(carry, _):
            b_t, b_f, acc = carry
            b_n = _tree_ppermute(b_f, geom.axr, geom.g)
            acc = acc + _local_mm(a, b_t, geom)
            acc = lax.ppermute(acc, geom.axc, _ring_perm(geom.g))
            return (b_f, b_n, acc), None

        (b_l, _, acc), _ = lax.scan(step, (b, b_f, acc0), None,
                                    length=geom.g - 1)
        acc = acc + _local_mm(a, b_l, geom)
        return lax.ppermute(acc, geom.axc, _ring_perm(geom.g))

    def step(carry, _):
        b_t, acc = carry
        b_n = _tree_ppermute(b_t, geom.axr, geom.g)   # prefetch next B tile
        acc = acc + _local_mm(a, b_t, geom)
        # route the partial C tile one hop toward its owner (the TPU
        # replacement for the paper's remote accumulation queue push)
        acc = lax.ppermute(acc, geom.axc,
                           [((d + 1) % geom.g, d) for d in range(geom.g)])
        return (b_n, acc), None

    (_, acc), _ = lax.scan(step, (b, acc0), None, length=geom.g)
    return acc


@register_algorithm("ring_c_bidir", a_placement=SKEW_ROWS,
                    b_placement=SKEW_COLS, wire=("a", "a", "b"), duplex=2,
                    packed_body=_packed_body_ring_c_bidir, packable=("a",),
                    wire_planner=_wire_planner_ring_c_bidir,
                    msgs_per_step=4)   # a_fwd, a_bwd, b_left, b_right
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
    b = _densify_b(b, geom)
    half = geom.tn // 2
    b_fwd = {"dense": b["dense"][:, :half]}
    b_bwd = {"dense": b["dense"][:, half:]}
    c_l0 = _pvary(jnp.zeros((geom.tm, half), dtype=geom.out_dtype), geom)
    c_r0 = _pvary(jnp.zeros((geom.tm, geom.tn - half), dtype=geom.out_dtype),
                  geom)
    if geom.overlap:
        # four streams, each with a two-slot buffer (see _body_ring_c)
        a_ff = _tree_ppermute(a, geom.axc, geom.g, +1)
        a_bf = _tree_ppermute(a, geom.axc, geom.g, -1)
        b_ff = _tree_ppermute(b_fwd, geom.axr, geom.g, +1)
        b_bf = _tree_ppermute(b_bwd, geom.axr, geom.g, -1)

        def step(carry, _):
            a_f, a_b, b_f, b_b, a_ff, a_bf, b_ff, b_bf, c_l, c_r = carry
            a_fn = _tree_ppermute(a_ff, geom.axc, geom.g, +1)
            a_bn = _tree_ppermute(a_bf, geom.axc, geom.g, -1)
            b_fn = _tree_ppermute(b_ff, geom.axr, geom.g, +1)
            b_bn = _tree_ppermute(b_bf, geom.axr, geom.g, -1)
            c_l = c_l + _local_mm(a_f, b_f, geom)
            c_r = c_r + _local_mm(a_b, b_b, geom)
            return (a_ff, a_bf, b_ff, b_bf, a_fn, a_bn, b_fn, b_bn,
                    c_l, c_r), None

        (a_fl, a_bl, b_fl, b_bl, _, _, _, _, c_l, c_r), _ = lax.scan(
            step, (a, a, b_fwd, b_bwd, a_ff, a_bf, b_ff, b_bf, c_l0, c_r0),
            None, length=geom.g - 1)
        c_l = c_l + _local_mm(a_fl, b_fl, geom)
        c_r = c_r + _local_mm(a_bl, b_bl, geom)
        return jnp.concatenate([c_l, c_r], axis=1)

    def step(carry, _):
        a_f, a_b, b_f, b_b, c_l, c_r = carry
        # prefetch both directions before the local matmuls (paper SS3.3)
        a_fn = _tree_ppermute(a_f, geom.axc, geom.g, +1)
        a_bn = _tree_ppermute(a_b, geom.axc, geom.g, -1)
        b_fn = _tree_ppermute(b_f, geom.axr, geom.g, +1)
        b_bn = _tree_ppermute(b_b, geom.axr, geom.g, -1)
        c_l = c_l + _local_mm(a_f, b_f, geom)
        c_r = c_r + _local_mm(a_b, b_b, geom)
        return (a_fn, a_bn, b_fn, b_bn, c_l, c_r), None

    (_, _, _, _, c_l, c_r), _ = lax.scan(
        step, (a, a, b_fwd, b_bwd, c_l0, c_r0), None, length=geom.g)
    return jnp.concatenate([c_l, c_r], axis=1)


# ---------------------------------------------------------------------------
# steal3d: static 3D work-grid dispatch from the stealing equilibrium
# ---------------------------------------------------------------------------
def _steal_plan_for(a_h: "DistMatrix", b_h: "DistMatrix", geom: _Geom,
                    wire: str = "padded",
                    assignment=None) -> "_steal3d.StealPlan":
    """Memoized steal3d planner (LPT assignment + pair lists + rounds).

    auto_select scoring shares this cache with plan construction: the one
    full build per operand structure (and wire mode) also serves the cost
    entry, and is reused outright if steal3d wins the race.

    An injected ``assignment`` (elastic recovery) bypasses the memo both
    ways: the plan is built fresh against it (``build_steal_plan`` runs
    its fail-fast invariant checks) and never enters the shared cache.
    """
    skey = a_h.structure_key() if isinstance(a_h, DistBSR) else None
    if not (wire == "packed" and isinstance(a_h, DistBSR)):
        wire = "padded"      # dense A has no packable steal3d traffic
    if assignment is not None:
        with _obs.span("plan_build.steal", wire=wire, injected=True):
            return _steal3d.build_steal_plan(a_h, b_h, geom, wire=wire,
                                             overlap=geom.overlap,
                                             assignment=assignment)
    key = (a_h.abstract_key(), b_h.abstract_key(), skey, wire, geom.overlap)
    sp = _STEAL_CACHE.get(key)
    if sp is None:
        with _obs.span("plan_build.steal", wire=wire):
            sp = _steal3d.build_steal_plan(a_h, b_h, geom, wire=wire,
                                           overlap=geom.overlap)
        _STEAL_CACHE[key] = sp
    return sp


def _steal3d_cost(alg: "Algorithm", geom: _Geom, a_h: "DistMatrix",
                  b_h: "DistMatrix", wire: str = "padded"
                  ) -> Dict[str, float]:
    """auto_select cost entry: the *simulated equilibrium* made a score.

    The flop term is the realized LPT makespan (pair capacity — executed
    block products on the most-loaded device, padding included) and the
    byte term counts panel gathers + moved tiles + owner reductions —
    packed to real blocks when ``wire="packed"`` — so ``algorithm="auto"``
    picks steal3d exactly when the plan-time stealing simulation says the
    equilibrium beats every owner-computes schedule's capacity-padded
    uniform work.
    """
    return dict(_steal_plan_for(a_h, b_h, geom, wire=wire).cost)


def _steal3d_perm(g: int, delta: int):
    return [(d, (d + delta) % g) for d in range(g)]


@register_algorithm("steal3d", style="bsp", wire=("a", "b", "c"),
                    static_planner=_steal_plan_for, cost_fn=_steal3d_cost,
                    packable=("a",))
def _body_steal3d(a, b, aux, geom: _Geom, splan: "_steal3d.StealPlan"):
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


# ---------------------------------------------------------------------------
# Distributed-matrix handles
# ---------------------------------------------------------------------------
def _canonical_placement(placement: str, g: int) -> str:
    """On a 1x1 grid every placement holds the one tile where it is, so
    all placements share the natural tree (no copy of the operand)."""
    if placement not in PLACEMENTS:
        raise ValueError(
            f"unknown placement {placement!r}; one of {PLACEMENTS}")
    return NATURAL if g == 1 else placement


def _place_bsr(t: TiledBSR, placement: str) -> TiledBSR:
    if placement == NATURAL:
        return t
    if placement in (SKEW_ROWS, SKEW_COLS):
        return skew_bsr(t, placement[len("skew_"):])
    if placement == STATIONARY_A:
        g = t.grid_shape[0]
        i = np.arange(g)[:, None]
        j = np.arange(g)[None, :]
        si, sj = j + 0 * i, (i + j) % g   # position (i,j) <- tile (j,(i+j)%g)
        take = lambda arr: arr[si, sj]
        return TiledBSR(
            blocks=take(t.blocks), rows=take(t.rows), cols=take(t.cols),
            counts=take(t.counts), shape=t.shape, block_size=t.block_size,
            grid_shape=t.grid_shape, capacity=t.capacity,
            logical_shape=t.logical_shape, row_block_perm=t.row_block_perm,
            col_block_perm=t.col_block_perm)
    raise ValueError(f"unknown placement {placement!r}; one of {PLACEMENTS}")


def _place_dense(x: jnp.ndarray, g: int, placement: str) -> jnp.ndarray:
    if placement == NATURAL:
        return x
    if placement == SKEW_ROWS:
        return skew_dense(x, g, "rows")
    if placement == SKEW_COLS:
        return skew_dense(x, g, "cols")
    if placement == STATIONARY_A:
        return place_b_for_stationary_a(x, g)
    raise ValueError(f"unknown placement {placement!r}; one of {PLACEMENTS}")


class DistMatrix:
    """A matrix distributed over a square ``g x g`` process grid.

    Subclasses cache placement transforms: ``placed(p)`` materializes the
    operand tree for placement ``p`` at most once per handle, the way the
    paper's DMatrix resolves its pointer directory once at construction.
    """

    kind = "abstract"

    @property
    def g(self) -> int:
        raise NotImplementedError

    @property
    def shape(self) -> Tuple[int, int]:      # padded global shape
        raise NotImplementedError

    @property
    def logical_shape(self) -> Tuple[int, int]:
        raise NotImplementedError

    @property
    def tile_shape(self) -> Tuple[int, int]:
        s = self.shape
        return s[0] // self.g, s[1] // self.g

    def placed(self, placement: str, commit: Optional[Callable] = None
               ) -> Dict[str, jnp.ndarray]:
        """The operand tree for ``placement``, cached on the handle.

        ``commit`` (tree -> tree) replaces the cached tree with its
        result, once: plans pass their mesh placement, so the operand
        then lives sharded over the devices instead of on the default
        device, and later calls move no operand data.
        """
        raise NotImplementedError

    def abstract_key(self) -> tuple:
        """Hashable abstract signature (shapes/dtypes, no data) for caching."""
        raise NotImplementedError

    def placements(self) -> Tuple[str, ...]:
        """Placement states materialized so far (diagnostics/tests)."""
        return tuple(self._placed)


class DistBSR(DistMatrix):
    """Handle for a block-sparse distributed matrix (wraps TiledBSR)."""

    kind = "bsr"

    def __init__(self, tiled: TiledBSR):
        if tiled.grid_shape[0] != tiled.grid_shape[1]:
            raise ValueError("square process grid required, got "
                             f"{tiled.grid_shape}")
        self.tiled = tiled
        self._placed: Dict[str, Dict[str, jnp.ndarray]] = {}

    @classmethod
    def from_tiled(cls, tiled: TiledBSR, *, balance: str = "none",
                   capacity="keep") -> "DistBSR":
        """Wrap a TiledBSR; ``balance != "none"`` re-tiles with balancing.

        Re-balancing an already-tiled matrix goes through a dense round
        trip (tiling is host-side construction, not a hot path); a tiled
        matrix that already carries a balance permutation is kept as-is.

        ``capacity`` controls the rebuilt uniform capacity: ``"keep"``
        (default) preserves the handle's existing value — a caller who
        pinned it to unify abstract shapes across matrices (plan-cache
        sharing) must not get a silently re-derived one — while ``None``
        re-derives the minimal capacity, realizing the balancing shrink
        (balancing never *increases* the needed capacity: the balancer
        falls back to the identity layout when it would), and ``"bucket"``
        re-derives it rounded up to a 1.25x bucket.  An int pins a new
        value.  A non-``"keep"`` capacity on a call that does not re-tile
        raises (it cannot be honored, and ignoring it would desync
        abstract keys).
        """
        if balance not in ("none", "rows", "cols", "auto"):
            raise ValueError(f"unknown balance {balance!r}; one of "
                             "('none', 'rows', 'cols', 'auto')")
        rebuilds = balance != "none" and tiled.row_block_perm is None \
            and tiled.col_block_perm is None
        if capacity != "keep" and not rebuilds:
            raise ValueError(
                "capacity can only be changed when from_tiled re-tiles "
                "(balance= on an unbalanced value); otherwise rebuild "
                "with TiledBSR.from_dense(capacity=...)")
        if rebuilds:
            m, n = tiled.logical_shape or tiled.shape
            dense = np.asarray(tiled.to_dense())[:m, :n]
            cap = tiled.capacity if capacity == "keep" else capacity
            tiled = TiledBSR.from_dense(
                dense, ProcessGrid(*tiled.grid_shape), tiled.block_size,
                capacity=cap, dtype=tiled.dtype, balance=balance)
        return cls(tiled)

    @classmethod
    def from_dense(cls, dense, *, g: int, block_size: int,
                   capacity="bucket", dtype=None,
                   balance: str = "none") -> "DistBSR":
        """Tile + wrap a dense array.

        Unlike raw ``TiledBSR.from_dense``, the default capacity here is
        ``"bucket"``: the minimal capacity rounded up to the next 1.25x
        bucket, so handles for near-identical sparsity patterns share
        abstract shapes — and therefore cached, jitted plans.  Pass
        ``capacity=None`` for the exact minimum or an int to pin.
        """
        return cls(TiledBSR.from_dense(dense, ProcessGrid(g, g), block_size,
                                       capacity=capacity, dtype=dtype,
                                       balance=balance))

    @property
    def g(self) -> int:
        return self.tiled.grid_shape[0]

    @property
    def shape(self) -> Tuple[int, int]:
        return self.tiled.shape

    @property
    def logical_shape(self) -> Tuple[int, int]:
        return self.tiled.logical_shape or self.tiled.shape

    @property
    def dtype(self):
        return self.tiled.dtype

    @property
    def block_size(self) -> int:
        return self.tiled.block_size

    @property
    def capacity(self) -> int:
        return self.tiled.capacity

    @property
    def counts(self):
        return self.tiled.counts

    @property
    def row_block_perm(self) -> Optional[Tuple[int, ...]]:
        """Row-block balance permutation (None unless ``balance="rows"``)."""
        return self.tiled.row_block_perm

    @property
    def col_block_perm(self) -> Optional[Tuple[int, ...]]:
        """Column-block balance permutation (``balance="cols"``)."""
        return self.tiled.col_block_perm

    def inv_row_perm(self) -> Optional[jnp.ndarray]:
        """Device array of the inverse balance permutation, cached on the
        handle so repeated plan calls don't recompute/re-upload it."""
        if self.tiled.row_block_perm is None:
            return None
        inv = getattr(self, "_inv_row_perm", None)
        if inv is None:
            inv = jnp.asarray(
                _schedule.invert_perm(self.tiled.row_block_perm))
            self._inv_row_perm = inv
        return inv

    def inv_col_perm(self) -> Optional[jnp.ndarray]:
        """Cached inverse of ``col_block_perm`` (see :meth:`inv_row_perm`)."""
        if self.tiled.col_block_perm is None:
            return None
        inv = getattr(self, "_inv_col_perm", None)
        if inv is None:
            inv = jnp.asarray(
                _schedule.invert_perm(self.tiled.col_block_perm))
            self._inv_col_perm = inv
        return inv

    def grid_structure(self) -> "_symbolic.GridStructure":
        """Host-side structural view of the stored slots (cached).

        One device read per handle lifetime, shared by everything that is
        specialized to the structure: the fingerprint, the symbolic phase,
        the steal3d planner and the packed wire layout.
        """
        s = getattr(self, "_grid_structure", None)
        if s is None:
            s = _symbolic.extract_structure(self.tiled)
            self._grid_structure = s
        return s

    def structure_key(self) -> str:
        """Fingerprint of the block *structure* (which slots hold data).

        Sparse-output, packed-wire and steal3d plans are specialized to
        the operands' structures (pair lists / consume maps are baked into
        the executable), so this joins the plan-cache key the way
        ``abstract_key`` does for shapes.  Cached on the handle.
        """
        return self.grid_structure().fingerprint

    def packed_operand(self) -> "_wire.PackedOperand":
        """Packed wire layout of this handle's structure (cached)."""
        po = getattr(self, "_packed_operand", None)
        if po is None:
            po = _wire.pack_operand(self.grid_structure())
            self._packed_operand = po
        return po

    def packed_wire(self, placement: str,
                    commit: Optional[Callable] = None
                    ) -> Dict[str, jnp.ndarray]:
        """Packed wire blocks for a placement: ``{"blocks": [g, g, wc,
        bs, bs]}`` — each tile's real blocks gathered into the packed
        prefix, trailing slots guaranteed zero.  Cached per placement,
        like :meth:`placed` (one gather per handle x placement lifetime),
        and ``commit`` as there.
        """
        cache = getattr(self, "_packed_placed", None)
        if cache is None:
            cache = self._packed_placed = {}
        placement = _canonical_placement(placement, self.g)
        tree = cache.get(placement)
        if tree is None:
            po = self.packed_operand()
            placed = self.placed(placement)["blocks"]
            tiles = _wire.placement_tiles(placement, self.g)
            pidx = po.pack_idx[tiles[..., 0], tiles[..., 1]]  # [g, g, wc]
            g = self.g
            ii = jnp.arange(g)[:, None, None]
            jj = jnp.arange(g)[None, :, None]
            tree = {"blocks": placed[ii, jj, jnp.asarray(pidx)]}
            cache[placement] = tree
        if commit is not None:
            tree = cache[placement] = commit(tree)
        return tree

    def densify(self) -> jnp.ndarray:
        """Dense logical-shape value (inverts balance perms, crops padding).

        Host-side convenience for tests/benchmarks — the whole point of
        sparse-output plans is that chained multiplies never need this.
        """
        d = self.tiled.to_dense()
        bs = self.block_size
        if self.tiled.row_block_perm is not None:
            inv = np.asarray(self.inv_row_perm())
            d = d.reshape(-1, bs, d.shape[1])[inv].reshape(d.shape)
        if self.tiled.col_block_perm is not None:
            inv = np.asarray(self.inv_col_perm())
            d = d.reshape(d.shape[0], -1, bs)[:, inv].reshape(d.shape)
        m, n = self.logical_shape
        return d[:m, :n]

    def footprint_bytes(self) -> int:
        """Bytes of the packed representation (blocks + structure arrays)."""
        t = self.tiled
        return int(t.blocks.nbytes + t.rows.nbytes + t.cols.nbytes
                   + t.counts.nbytes)

    def placed(self, placement: str, commit: Optional[Callable] = None
               ) -> Dict[str, jnp.ndarray]:
        placement = _canonical_placement(placement, self.g)
        tree = self._placed.get(placement)
        if tree is None:
            t = _place_bsr(self.tiled, placement)
            tree = {"blocks": t.blocks, "rows": t.rows, "cols": t.cols}
            self._placed[placement] = tree
        if commit is not None:
            tree = self._placed[placement] = commit(tree)
        return tree

    def abstract_key(self) -> tuple:
        t = self.tiled
        return ("bsr", t.shape, t.grid_shape, t.block_size, t.capacity,
                jnp.dtype(t.dtype).name)


class DistDense(DistMatrix):
    """Handle for a dense distributed matrix (grid-padded global array)."""

    kind = "dense"

    def __init__(self, data, g: int,
                 logical_shape: Optional[Tuple[int, int]] = None):
        data = jnp.asarray(data)
        if data.ndim != 2:
            raise ValueError(f"expected a 2-D array, got shape {data.shape}")
        if data.shape[0] % g or data.shape[1] % g:
            raise ValueError(
                f"padded shape {data.shape} not divisible by grid size {g}; "
                "use DistDense.from_global to pad")
        self.data = data
        self._g = g
        self._logical = tuple(logical_shape or data.shape)
        self._placed: Dict[str, Dict[str, jnp.ndarray]] = {}

    @classmethod
    def from_global(cls, x, g: int, *, rows_pad: Optional[int] = None,
                    cols_pad: Optional[int] = None) -> "DistDense":
        """Wrap a global array, zero-padding each dim to a multiple of g."""
        x = jnp.asarray(x)
        m, n = x.shape
        rp = pad_to_multiple(m, g) if rows_pad is None else rows_pad
        cp = pad_to_multiple(n, g) if cols_pad is None else cols_pad
        if rp < m or cp < n or rp % g or cp % g:
            raise ValueError(f"bad padded shape ({rp}, {cp}) for array "
                             f"{x.shape} on a {g}x{g} grid")
        if (rp, cp) != (m, n):
            x = jnp.zeros((rp, cp), x.dtype).at[:m, :n].set(x)
        return cls(x, g, logical_shape=(m, n))

    @classmethod
    def for_rhs(cls, x, a: DistMatrix, *, allow_pad: bool = False
                ) -> "DistDense":
        """Wrap the right operand of ``a @ x``, matching a's padded K dim.

        The inner dimension must equal a's logical or padded column count;
        anything smaller is only zero-padded with an explicit
        ``allow_pad=True`` (silent padding hides shape bugs).
        """
        x = jnp.asarray(x)
        k = x.shape[0]
        k_pad, k_log = a.shape[1], a.logical_shape[1]
        if k > k_pad:
            raise ValueError(
                f"inner dimensions disagree: right operand has {k} rows, "
                f"left operand has only {k_pad} (padded) columns")
        if k not in (k_pad, k_log) and not allow_pad:
            raise ValueError(
                f"inner dimension mismatch: right operand has {k} rows but "
                f"the left operand has {k_log} logical / {k_pad} padded "
                "columns; pass allow_pad=True to zero-pad explicitly")
        return cls.from_global(x, a.g, rows_pad=k_pad)

    @property
    def g(self) -> int:
        return self._g

    @property
    def shape(self) -> Tuple[int, int]:
        return self.data.shape

    @property
    def logical_shape(self) -> Tuple[int, int]:
        return self._logical

    @property
    def dtype(self):
        return self.data.dtype

    def placed(self, placement: str, commit: Optional[Callable] = None
               ) -> Dict[str, jnp.ndarray]:
        placement = _canonical_placement(placement, self._g)
        tree = self._placed.get(placement)
        if tree is None:
            tree = {"dense": _place_dense(self.data, self._g, placement)}
            self._placed[placement] = tree
        if commit is not None:
            tree = self._placed[placement] = commit(tree)
        return tree

    def abstract_key(self) -> tuple:
        return ("dense", self.data.shape, self._g,
                jnp.dtype(self.data.dtype).name)


def _reshard_bsr(h: DistBSR, g: int, capacity) -> DistBSR:
    t = h.tiled
    if t.row_block_perm is not None or t.col_block_perm is not None:
        raise ValueError(
            "reshard does not support balanced handles (the balance "
            "permutation is tied to the old grid); rebuild with "
            "DistBSR.from_dense(balance=...) on the new grid")
    bs = t.block_size
    g_old = h.g
    s = h.grid_structure()          # host-side rows/cols/real (cached)
    nbr_old, nbc_old = s.tile_nbr, s.tile_nbc
    m, n = h.logical_shape
    tm = pad_to_multiple(ceil_div(m, g), bs)
    tn = pad_to_multiple(ceil_div(n, g), bs)
    nbr, nbc = tm // bs, tn // bs
    rows_h = np.asarray(s.rows)
    cols_h = np.asarray(s.cols)
    real_h = np.asarray(s.real)
    store_old = rows_h.shape[2]
    # Bucket every real stored block by its *new* tile, in (row, col)
    # order — the order TiledBSR.from_dense's nonzero scan would produce.
    per_tile: Dict[Tuple[int, int], List[Tuple[int, int, int]]] = {}
    for i in range(g_old):
        for j in range(g_old):
            for slot in np.nonzero(real_h[i, j])[0]:
                gbr = i * nbr_old + int(rows_h[i, j, slot])
                gbc = j * nbc_old + int(cols_h[i, j, slot])
                src = (i * g_old + j) * store_old + int(slot)
                key = (gbr // nbr, gbc // nbc)
                per_tile.setdefault(key, []).append(
                    (gbr % nbr, gbc % nbc, src))
    max_nnzb = max((len(v) for v in per_tile.values()), default=0)
    if capacity == "bucket":
        cap = bucket_capacity(max_nnzb)
    elif capacity is None:
        cap = max_nnzb
    else:
        cap = int(capacity)
        if cap < max_nnzb:
            raise ValueError(f"capacity {cap} < max tile nnzb {max_nnzb}")
    store = cap + nbr
    rows_new = np.zeros((g, g, store), dtype=np.int32)
    cols_new = np.zeros((g, g, store), dtype=np.int32)
    src_new = np.full((g, g, store), -1, dtype=np.int64)
    counts_new = np.zeros((g, g), dtype=np.int32)
    cov = np.arange(nbr, dtype=np.int32)
    for i in range(g):
        for j in range(g):
            ent = sorted(per_tile.get((i, j), []))
            counts_new[i, j] = len(ent)
            r = np.array([e[0] for e in ent], dtype=np.int32)
            c = np.array([e[1] for e in ent], dtype=np.int32)
            src = np.array([e[2] for e in ent], dtype=np.int64)
            # pad to uniform capacity the way BSR.with_capacity does
            # (repeat the last coordinate, zero block), then merge the
            # coverage blocks in sorted order like _augment_tile
            pad = cap - len(ent)
            last_r = r[-1] if len(ent) else np.int32(0)
            last_c = c[-1] if len(ent) else np.int32(0)
            r = np.concatenate([r, np.full(pad, last_r, np.int32), cov])
            c = np.concatenate([c, np.full(pad, last_c, np.int32),
                                np.zeros(nbr, np.int32)])
            src = np.concatenate([src, np.full(pad + nbr, -1, np.int64)])
            order = np.argsort(r, kind="stable")
            rows_new[i, j] = r[order]
            cols_new[i, j] = c[order]
            src_new[i, j] = src[order]
    # One device gather moves every block value to its new tile slot: no
    # host round-trip of block data, no dense materialization.
    old_flat = t.blocks.reshape(-1, bs, bs)
    pool = jnp.concatenate(
        [old_flat, jnp.zeros((1, bs, bs), t.blocks.dtype)])
    idx = np.where(src_new < 0, old_flat.shape[0], src_new)
    blocks_new = pool[jnp.asarray(idx.reshape(-1))].reshape(
        g, g, store, bs, bs)
    return DistBSR(TiledBSR(
        blocks=blocks_new, rows=jnp.asarray(rows_new),
        cols=jnp.asarray(cols_new), counts=jnp.asarray(counts_new),
        shape=(tm * g, tn * g), block_size=bs, grid_shape=(g, g),
        capacity=cap, logical_shape=(m, n)))


def reshard(h: DistMatrix, g: int, *, capacity="bucket") -> DistMatrix:
    """Re-tile a handle onto a ``g x g`` grid without a host round-trip.

    The elastic-recovery path: after device loss the surviving mesh gets a
    smaller grid (``runtime.elastic.choose_grid_shape``) and the live
    operands must move onto it.  Dense handles re-pad the logical region;
    BSR handles re-bucket their stored blocks by new-tile coordinates on
    the host's cached *structure* view (integer index arithmetic only)
    and move the block *values* with a single device gather — the data
    plane never leaves the devices and nothing is re-densified.

    ``capacity`` is the rebuilt uniform tile capacity (``"bucket"`` |
    ``None`` | int, as in :meth:`DistBSR.from_dense`).  Balanced BSR
    handles are rejected: their permutation is tied to the old grid.
    Returns a new handle (``h`` itself when ``g`` already matches).
    """
    if g < 1:
        raise ValueError(f"grid size must be >= 1, got {g}")
    if isinstance(h, DistBSR):
        if g == h.g:
            return h
        return _reshard_bsr(h, g, capacity)
    if isinstance(h, DistDense):
        if g == h.g:
            return h
        m, n = h.logical_shape
        return DistDense.from_global(h.data[:m, :n], g)
    raise TypeError(f"cannot reshard {type(h).__name__}")


# ---------------------------------------------------------------------------
# Mesh preparation / validation
# ---------------------------------------------------------------------------
def validate_mesh(mesh, g: int, axis_row: str, axis_col: str) -> None:
    """Fail fast (and clearly) on a mesh that can't carry the g x g grid."""
    names = tuple(mesh.axis_names)
    if axis_row not in names or axis_col not in names:
        raise ValueError(
            f"mesh axes {names} do not include the required axes "
            f"({axis_row!r}, {axis_col!r}); build one with "
            f"make_grid_mesh({g}, {axis_row!r}, {axis_col!r})")
    if len(names) != 2:
        raise ValueError(
            f"expected a 2-axis ({axis_row!r}, {axis_col!r}) mesh, got axes "
            f"{names}")
    shape = dict(mesh.shape)
    got = (shape[axis_row], shape[axis_col])
    if got != (g, g):
        raise ValueError(
            f"mesh shape {axis_row}={got[0]}, {axis_col}={got[1]} does not "
            f"match the {g}x{g} process grid of the operands")


def _prep_mesh(mesh, g: int, axis_row: str, axis_col: str):
    if mesh is None:
        return make_grid_mesh(g, axis_row, axis_col)
    validate_mesh(mesh, g, axis_row, axis_col)
    return mesh


# ---------------------------------------------------------------------------
# Plans
# ---------------------------------------------------------------------------
_TRACE_HOOKS: List[Callable] = []


def add_trace_hook(hook: Callable) -> Callable:
    """Register ``hook(plan)`` to fire once per executable (re)trace."""
    _TRACE_HOOKS.append(hook)
    return hook


def remove_trace_hook(hook: Callable) -> None:
    _TRACE_HOOKS.remove(hook)


def _tree_keys(abstract_key: tuple) -> Tuple[str, ...]:
    return ("blocks", "rows", "cols") if abstract_key[0] == "bsr" \
        else ("dense",)


def _specs_for_keys(keys: Tuple[str, ...], axr: str, axc: str) -> Dict:
    out = {}
    for k in keys:
        if k == "dense":
            out[k] = P(axr, axc)
        elif k == "blocks":
            out[k] = P(axr, axc, None, None, None)
        else:  # rows / cols
            out[k] = P(axr, axc, None)
    return out


def _local_view(tree: Dict) -> Dict:
    """Strip the leading (1, 1) grid dims of TiledBSR leaves inside shard_map."""
    return {k: (v if k == "dense" else v[0, 0]) for k, v in tree.items()}


def _key_dtype(abstract_key: tuple):
    return abstract_key[5] if abstract_key[0] == "bsr" else abstract_key[3]


def _cost_model(alg: Algorithm, geom: _Geom, a_key: tuple, b_key: tuple,
                symbolic: Optional["SymbolicProduct"] = None,
                wire_caps: Optional[Dict[str, int]] = None
                ) -> Dict[str, float]:
    """Per-step wire volume / executed flops of one plan execution.

    Reflects what the bodies actually move and execute: the A tile rides in
    its stored *pre-augmented* BSR form (``capacity + tile block-rows``
    block products per step, padding included — the quantity the static
    scheduler balances); the B tile rides *densified* regardless of kind
    (``_densify_b`` hoists the scatter out of the scanned step); ``wire``
    may name a tile twice (bidirectional schedules) and ``duplex`` credits
    full-duplex links in :func:`_predicted_time`, not here.

    With ``symbolic`` (a sparse-output plan), the model charges what the
    sparse path actually does instead: B rides in stored block form (never
    densified), the step executes ``pair_capacity`` block-pair products
    (padding included), and C is the packed slot array — so sparse-output
    schedules are scored on their true output traffic, which is what makes
    ``output="auto"`` flip for hypersparse products.

    With ``wire_caps`` (a packed-wire plan: ``{"a": wc}`` and/or
    ``{"b": wc}``), a packed operand is charged blocks-only at its wire
    capacity — no coverage padding, no rows/cols index traffic, and for a
    packed sparse B no densified tile — and the packed A step executes
    the gathered coverage-augmented list (``wc + tile block-rows``
    products) instead of the stored stride.  This is what lets
    :func:`auto_select` scores flip where packing changes the
    comm/compute trade.
    """
    g = geom.g
    wire_caps = wire_caps or {}
    if symbolic is not None:
        bs = symbolic.block_size
        store_a = a_key[4] + geom.a_nbr
        store_b = b_key[4] + geom.b_nbr
        wa = np.dtype(_key_dtype(a_key)).itemsize
        wb = np.dtype(_key_dtype(b_key)).itemsize
        a_slots = wire_caps.get("a", store_a)
        b_slots = wire_caps.get("b", store_b)
        a_bytes = a_slots * bs * bs * wa
        b_bytes = b_slots * bs * bs * wb
        c_bytes = symbolic.store_capacity * bs * bs \
            * np.dtype(geom.out_dtype).itemsize
        flops_step = 2 * symbolic.pair_capacity * bs ** 3
        tiles = {"a": a_bytes, "b": b_bytes, "c": c_bytes}
        return _assemble_cost(alg, g, a_bytes, b_bytes, c_bytes, flops_step,
                              tiles)
    if a_key[0] == "bsr":
        bs, cap = a_key[3], a_key[4]
        wa = np.dtype(_key_dtype(a_key)).itemsize
        if "a" in wire_caps:
            wc = wire_caps["a"]             # packed: blocks only
            a_bytes = wc * bs * bs * wa
            # the step executes the gathered augmented list, never more
            # than the padded stride
            slots = min(wc + geom.a_nbr, cap + geom.a_nbr)
            flops_step = 2 * slots * bs * bs * geom.tn
        else:
            store = cap + geom.a_nbr        # pre-augmented stored slots
            a_bytes = store * bs * bs * wa \
                + store * 2 * 4             # + rows/cols int32
            flops_step = 2 * store * bs * bs * geom.tn
    else:
        tk = a_key[1][1] // g
        a_bytes = geom.tm * tk * np.dtype(_key_dtype(a_key)).itemsize
        flops_step = 2 * geom.tm * tk * geom.tn
    wb = np.dtype(_key_dtype(b_key)).itemsize
    if "b" in wire_caps and b_key[0] == "bsr":
        b_bytes = wire_caps["b"] * b_key[3] * b_key[3] * wb
    else:
        tk_b = b_key[1][0] // g
        b_bytes = tk_b * geom.tn * wb
    c_bytes = geom.tm * geom.tn * np.dtype(geom.out_dtype).itemsize
    tiles = {"a": a_bytes, "b": b_bytes, "c": c_bytes}
    return _assemble_cost(alg, g, a_bytes, b_bytes, c_bytes, flops_step,
                          tiles)


def _assemble_cost(alg: Algorithm, g: int, a_bytes, b_bytes, c_bytes,
                   flops_step, tiles) -> Dict[str, float]:
    step_bytes = sum(tiles[t] for t in alg.wire)
    if alg.wire_amortized:
        step_bytes = step_bytes * (g - 1) / g
    total_flops = float(flops_step * g)
    total_bytes = float(step_bytes * g)
    return {
        "steps": float(g),
        "flops_per_step": float(flops_step),
        "net_bytes_per_step": float(step_bytes),
        "total_flops": total_flops,
        "total_net_bytes": total_bytes,
        "ai_net": total_flops / total_bytes if total_bytes else float("inf"),
        "ai_local": total_flops / (g * (a_bytes + b_bytes) + c_bytes),
    }


def _overlap_eff(alg: Algorithm, machine: "_roofline.Machine",
                 overlap: str) -> float:
    """The comm-hiding fraction the cost model credits this schedule.

    ``"off"`` serializes everything.  ``"on"`` credits the machine's
    fitted ``overlap_eff`` to every schedule whose per-step transfers the
    split-step bodies can double-buffer — i.e. all but the wire-amortized
    ones (summa_ag's single up-front gather gates all compute; nothing to
    hide under).  ``"auto"`` (the scoring default) credits it only to the
    RDMA-style prefetch schedules, which reproduces the legacy
    sum-vs-max scoring exactly at the preset ``overlap_eff = 1.0``:
    bulk-synchronous schedules pay ``comp + comm`` (a barrier per stage),
    rings pay ``comp + max(0, comm - comp) = max(comp, comm)`` — the
    paper's SS3.3 overlap claim as a scheduling preference.
    """
    if overlap == "off":
        return 0.0
    if overlap == "on":
        return 0.0 if alg.wire_amortized else machine.overlap_eff
    return machine.overlap_eff if alg.style != "bsp" else 0.0


def _time_breakdown(cm: Dict[str, float], alg: Algorithm,
                    machine: "_roofline.Machine",
                    overlap: str = "auto") -> Dict[str, float]:
    """Alpha-beta-gamma time decomposition for one execution.

    Compute time is capped by the local roofline; wire time is serialized
    bytes over the per-chip link share (credited for ``duplex``) plus a
    per-message alpha term (``machine.hop_latency``).  The overlap term
    (:func:`_overlap_eff`, ``machine.overlap_eff``) converts raw comm
    into *exposed* comm — ``max(0, comm - eff * comp)`` — and the
    predicted seconds are ``comp + exposed``.
    """
    t_comp = cm["total_flops"] / _roofline.local_peak(cm["ai_local"], machine)
    if "n_msgs" in cm:
        # structure-dependent schedules (steal3d) count their actual
        # collective rounds in the cost model instead of wire x steps
        msgs = cm["n_msgs"]
    else:
        n_msgs = alg.msgs_per_step if alg.msgs_per_step is not None \
            else len(alg.wire)
        msgs = n_msgs * (1.0 if alg.wire_amortized else cm["steps"])
    t_comm = cm["total_net_bytes"] / (machine.net_bw * alg.duplex) \
        + msgs * machine.hop_latency
    eff = _overlap_eff(alg, machine, overlap)
    exposed = max(0.0, t_comm - eff * t_comp)
    return {
        "t_comp": t_comp,
        "t_comm": t_comm,
        "t_comm_exposed": exposed,
        "msgs": float(msgs),
        "duplex": float(alg.duplex),
        "overlap_eff": eff,
        "predicted_s": t_comp + exposed,
    }


def _predicted_time(cm: Dict[str, float], alg: Algorithm,
                    machine: "_roofline.Machine",
                    overlap: str = "auto") -> float:
    """Predicted seconds for one execution — the auto-select score."""
    return _time_breakdown(cm, alg, machine, overlap)["predicted_s"]


class MatmulPlan:
    """A reusable distributed multiply: placement + one compiled executable.

    Create via :func:`plan_matmul`; execute with ``plan(a, b)``.  The
    executable is ``jax.jit(shard_map(body))`` built once at plan time, so
    repeated calls with the same abstract operand shapes re-use the compiled
    program (``plan.traces`` counts actual traces).
    """

    def __init__(self, algorithm: Algorithm, geom: _Geom, mesh,
                 a_key: tuple, b_key: tuple, allow_pad: bool = False,
                 requested: Optional[str] = None,
                 auto_scores: Optional[Dict[str, float]] = None,
                 symbolic: Optional["SymbolicProduct"] = None,
                 steal: Optional["_steal3d.StealPlan"] = None,
                 wire: str = "padded", packs: Tuple[str, ...] = (),
                 wire_aux: Optional[Dict[str, np.ndarray]] = None,
                 wire_caps: Optional[Dict[str, int]] = None,
                 wire_fps: Optional[Dict[str, str]] = None,
                 overlap: str = "auto"):
        self.algorithm = algorithm
        self.geom = geom
        # the overlap mode this plan was built under ("auto"|"on"|"off");
        # geom.overlap holds the resolved body structure, this records
        # the request for cost reporting (cost_model / predicted_cost)
        self.overlap = overlap
        self.mesh = mesh
        self._a_key = a_key
        self._b_key = b_key
        self._allow_pad = allow_pad
        # Introspection: what the request that FIRST BUILT this plan asked
        # for ("auto" or a name) and, if auto ever selected this plan, the
        # candidate scores from that selection.  Cached plans are shared
        # across requests, so these describe the plan's provenance, not
        # necessarily the current call (auto re-scores on every call; see
        # plan_matmul).
        self.requested = requested or algorithm.name
        self.auto_scores = auto_scores
        self.symbolic = symbolic
        self.steal = steal
        # Packed-wire state: which operands ship packed ("a"/"b"), their
        # wire capacities (the cost-model byte terms) and the structure
        # fingerprints the consume maps were built for (the call guard).
        self.wire = wire
        self._packs = packs
        self._wire_caps = wire_caps
        self._wire_fps = wire_fps or {}
        self.traces = 0
        # static-verification memo: modes this plan already passed
        # ("fast"/"full") — revalidating a cached plan is a set lookup
        self._validated: set = set()
        specs = (_specs_for_keys(_tree_keys(a_key), geom.axr, geom.axc),
                 _specs_for_keys(_tree_keys(b_key), geom.axr, geom.axc))

        if steal is not None:
            # steal3d plan: the executable is specialized to the LPT
            # assignment — pair lists, move-round gather indices and
            # reduce-round slot selectors ride as a third operand tree
            # (committed in their mesh sharding once, like sparse-output
            # pair lists); only A's block data is sharded in for sparse A.
            body = algorithm.body
            aux_specs = {k: P(geom.axr, geom.axc, *(None,) * (v.ndim - 2))
                         for k, v in steal.aux.items()}
            self._aux = {
                k: jax.device_put(
                    np.ascontiguousarray(v),
                    jax.sharding.NamedSharding(mesh, aux_specs[k]))
                for k, v in steal.aux.items()}

            def fn(a, b, aux):
                self.traces += 1          # runs at trace time only
                for hook in list(_TRACE_HOOKS):
                    hook(self)
                return body(_local_view(a), _local_view(b),
                            {k: v[0, 0] for k, v in aux.items()}, geom,
                            steal)

            a_keys = ("blocks",) if a_key[0] == "bsr" else ("dense",)
            in_specs = (_specs_for_keys(a_keys, geom.axr, geom.axc),
                        specs[1], aux_specs)
            out_specs = P(geom.axr, geom.axc)
        elif symbolic is None and wire_aux is not None:
            # Packed-wire dense-output plan: the executable is specialized
            # to the packed operands' structures — the consume maps
            # (augmented-list gathers / densify-by-gather maps built by
            # repro.core.wire) ride as a third operand tree, committed in
            # their mesh sharding once like steal3d aux; a packed operand
            # ships blocks-only at the wire capacity.
            packed_body = algorithm.packed_body
            aux_specs = {k: P(geom.axr, geom.axc, *(None,) * (v.ndim - 2))
                         for k, v in wire_aux.items()}
            self._aux = {
                k: jax.device_put(
                    np.ascontiguousarray(v),
                    jax.sharding.NamedSharding(mesh, aux_specs[k]))
                for k, v in wire_aux.items()}

            def fn(a, b, aux):
                self.traces += 1          # runs at trace time only
                for hook in list(_TRACE_HOOKS):
                    hook(self)
                return packed_body(_local_view(a), _local_view(b),
                                   {k: v[0, 0] for k, v in aux.items()},
                                   geom)

            blocks_spec = {"blocks": P(geom.axr, geom.axc, None, None,
                                       None)}
            in_specs = (blocks_spec if "a" in packs else specs[0],
                        blocks_spec if "b" in packs else specs[1],
                        aux_specs)
            out_specs = P(geom.axr, geom.axc)
        elif symbolic is None:
            body = algorithm.body

            def fn(a, b):
                self.traces += 1          # runs at trace time only
                for hook in list(_TRACE_HOOKS):
                    hook(self)
                return body(_local_view(a), _local_view(b), geom)

            in_specs, out_specs = specs, P(geom.axr, geom.axc)
        else:
            # Sparse-output plan: the executable is specialized to the
            # operands' structures — pair lists (scheduled per the
            # algorithm's k_order) ride as a third operand tree, only the
            # block data of A and B is sharded in, and the result is the
            # packed per-tile slot array wrapped into a DistBSR by
            # _epilogue_sparse.  Under wire="packed" the operands' blocks
            # ride in packed wire form and the stored->packed slot map is
            # already composed into the (remapped) pair lists.
            sparse_body = algorithm.sparse_body
            sched = symbolic.scheduled_pairs(
                algorithm.k_order,
                pair_a=None if wire_aux is None else wire_aux.get("pa"),
                pair_b=None if wire_aux is None else wire_aux.get("pb"))
            # Pair lists are plan-lifetime constants; commit them in their
            # mesh sharding once so repeated calls don't re-transfer them
            # to every device (measurably dominates small multiplies).
            pair_sharding = jax.sharding.NamedSharding(
                mesh, P(geom.axr, geom.axc, None, None))
            self._pairs = {k: jax.device_put(np.asarray(v, dtype=np.int32),
                                             pair_sharding)
                           for k, v in sched.items()}
            self._c_rows = jnp.asarray(symbolic.c_rows, dtype=jnp.int32)
            self._c_cols = jnp.asarray(symbolic.c_cols, dtype=jnp.int32)
            self._c_counts = jnp.asarray(symbolic.c_counts, dtype=jnp.int32)

            def fn(a, b, pairs):
                self.traces += 1          # runs at trace time only
                for hook in list(_TRACE_HOOKS):
                    hook(self)
                c = sparse_body(_local_view(a), _local_view(b),
                                _local_view(pairs), geom)
                return c[None, None]      # restore the (1, 1) grid dims

            blocks_spec = {"blocks": P(geom.axr, geom.axc, None, None, None)}
            pair_spec = {k: P(geom.axr, geom.axc, None, None)
                         for k in ("pa", "pb", "ps")}
            in_specs = (blocks_spec, blocks_spec, pair_spec)
            out_specs = P(geom.axr, geom.axc, None, None, None)

        # named_scope is trace-time-only HLO metadata: XLA profiles (and
        # hlo_analysis.scope_op_counts) attribute device time to this
        # plan's schedule by name, at zero runtime cost and zero added
        # retraces (tests assert plan.traces stays 1).
        inner_fn = fn
        scope_label = f"plan.{algorithm.name}.{wire}"

        def fn(*operands):
            with jax.named_scope(scope_label):
                return inner_fn(*operands)

        self._exec = jax.jit(jax.shard_map(
            fn, mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            # pallas_call's out_shape carries no vma annotation; the engine's
            # collectives are explicit, so skip the varying-axes checker.
            check_vma=False))

    @property
    def kind(self) -> str:
        """"spmm" | "spgemm" | "dense" — what this plan dispatches to."""
        a_sparse = self._a_key[0] == "bsr"
        b_sparse = self._b_key[0] == "bsr"
        if a_sparse:
            return "spgemm" if b_sparse else "spmm"
        return "dense"

    @property
    def output(self) -> str:
        """"sparse" (returns a DistBSR) or "dense" (returns an array)."""
        return "dense" if self.symbolic is None else "sparse"

    def __call__(self, a, b):
        # Tracing off (the default): straight to the executable — no clock
        # reads, no blocking, async dispatch preserved.
        if not _obs.enabled():
            return self._execute(a, b)
        t0 = time.perf_counter()
        sp = _obs.span(f"multiply.{self.algorithm.name}", kind=self.kind,
                       wire=self.wire, output=self.output,
                       overlap=self.overlap)
        with sp:
            out = self._execute(a, b)
            # Per-multiply seconds follow the sync_elapsed discipline:
            # block on the result tree, then read the clock.
            tree = out.tiled.blocks if isinstance(out, DistBSR) else out
            measured = _obs.sync_elapsed(t0, tree)
            sp.note(measured_s=measured)
        machine = _DRIFT_MACHINE or _roofline.TPU_V5E
        cm = self.cost_model()
        _obs.record_drift(
            self.algorithm.name, self.wire, self.overlap,
            predicted_s=_predicted_time(cm, self.algorithm, machine,
                                        self.overlap),
            measured_s=measured, cm=cm, kind=self.kind,
            machine=machine.name)
        return out

    def _operands(self, a_h: DistMatrix, b_h: DistMatrix) -> tuple:
        """Guard-check coerced handles and build the executable's operand
        tuple — exactly the arguments ``self._exec`` is called with.

        Shared by ``_execute`` and the static analyzer
        (``repro.analysis.jaxpr_lint.trace_plan``), so the linted trace is
        the executed trace: packed wire trees, steal3d aux and sparse
        pair lists included.
        """
        if (a_h.abstract_key(), b_h.abstract_key()) != (self._a_key,
                                                        self._b_key):
            raise ValueError(
                "operands do not match this plan's abstract shapes "
                f"(plan: {self._a_key} @ {self._b_key}, got "
                f"{a_h.abstract_key()} @ {b_h.abstract_key()}); build a new "
                "plan with plan_matmul")
        on_mesh = self._on_mesh
        pl_a, pl_b = self.algorithm.a_placement, self.algorithm.b_placement
        if self.steal is not None:
            if self._a_key[0] == "bsr":
                if a_h.structure_key() != self.steal.a_fingerprint:
                    raise ValueError(
                        "left operand's sparsity structure does not match "
                        "this steal3d plan (the LPT assignment and pair "
                        "lists are specialized to the structure); build a "
                        "new plan with plan_matmul")
                if self.steal.wire == "packed":
                    a_tree = a_h.packed_wire(pl_a, on_mesh)
                else:
                    a_tree = {"blocks": a_h.placed(pl_a, on_mesh)["blocks"]}
            else:
                a_tree = a_h.placed(pl_a, on_mesh)
            return (a_tree, b_h.placed(pl_b, on_mesh), self._aux)
        packed = self.wire == "packed"
        if self.symbolic is not None:
            sym = self.symbolic
            if (a_h.structure_key(), b_h.structure_key()) != \
                    (sym.a_fingerprint, sym.b_fingerprint):
                raise ValueError(
                    "operands' sparsity structure does not match this "
                    "sparse-output plan (pair lists are specialized to the "
                    "structure); build a new plan with plan_matmul")
            a_tree = a_h.packed_wire(pl_a, on_mesh) if packed \
                else {"blocks": a_h.placed(pl_a, on_mesh)["blocks"]}
            b_tree = b_h.packed_wire(pl_b, on_mesh) if packed \
                else {"blocks": b_h.placed(pl_b, on_mesh)["blocks"]}
            return (a_tree, b_tree, self._pairs)
        if packed:
            for who, h in (("a", a_h), ("b", b_h)):
                if who in self._packs \
                        and h.structure_key() != self._wire_fps.get(who):
                    raise ValueError(
                        f"{'left' if who == 'a' else 'right'} operand's "
                        "sparsity structure does not match this packed-wire "
                        "plan (the consume maps are specialized to the "
                        "structure); build a new plan with plan_matmul")
            a_tree = a_h.packed_wire(pl_a, on_mesh) if "a" in self._packs \
                else a_h.placed(pl_a, on_mesh)
            b_tree = b_h.packed_wire(pl_b, on_mesh) if "b" in self._packs \
                else b_h.placed(pl_b, on_mesh)
            return (a_tree, b_tree, self._aux)
        return (a_h.placed(pl_a, on_mesh), b_h.placed(pl_b, on_mesh))

    def _on_mesh(self, tree: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
        """An operand tree committed to this plan's mesh: tile (i, j) on
        device (i, j), the layout the executable's in_specs ask for."""
        want = {k: jax.sharding.NamedSharding(
                    self.mesh,
                    P(self.geom.axr, self.geom.axc, *(None,) * (v.ndim - 2)))
                for k, v in tree.items()}
        if all(v.sharding.is_equivalent_to(want[k], v.ndim)
               for k, v in tree.items()):
            return tree
        return jax.device_put(tree, want)

    def lower(self, a, b):
        """The plan's executable lowered for these operands (``.compile()``
        it for ``as_text()`` / ``memory_analysis()``); traces if needed."""
        a_h, b_h = _coerce_pair(a, b, g=self.geom.g,
                                allow_pad=self._allow_pad)
        return self._exec.lower(*self._operands(a_h, b_h))

    def _execute(self, a, b):
        a_h, b_h = _coerce_pair(a, b, g=self.geom.g,
                                allow_pad=self._allow_pad)
        c = self._exec(*self._operands(a_h, b_h))
        if self.symbolic is not None:
            return self._epilogue_sparse(c, a_h, b_h)
        return self._epilogue(c, a_h, b_h)

    def _epilogue_sparse(self, c_blocks: jnp.ndarray, a_h: DistBSR,
                         b_h: DistBSR) -> DistBSR:
        """Wrap the packed numeric result into a DistBSR handle.

        The symbolic layout already satisfies the TiledBSR storage contract
        (row-sorted, coverage-augmented, uniformly padded), so the handle
        is immediately usable as an operand of further multiplies — chained
        A @ A @ A never densifies or re-tiles.
        """
        sym = self.symbolic
        tiled = TiledBSR(
            blocks=c_blocks, rows=self._c_rows, cols=self._c_cols,
            counts=self._c_counts, shape=sym.shape,
            block_size=sym.block_size, grid_shape=(sym.g, sym.g),
            capacity=sym.capacity,
            logical_shape=(a_h.logical_shape[0], b_h.logical_shape[1]))
        return DistBSR(tiled)

    def _epilogue(self, c: jnp.ndarray, a_h: DistMatrix,
                  b_h: DistMatrix) -> jnp.ndarray:
        """Shared output fix-up: unskew, un-balance, crop padding.

        One copy for all operand kinds — the sparse and dense paths get
        identical ``logical_shape`` cropping semantics.  A balanced left
        operand permuted its global row blocks before tiling; C inherits
        that permutation, so it is inverted here (after the tile-grid
        unskew, before the crop) to keep balanced and unbalanced plans
        bit-compatible.
        """
        if self.algorithm.unskew_out == "rows":
            c = unskew_c_rows(c, self.geom.g)
        elif self.algorithm.unskew_out is not None:
            raise ValueError(
                f"unknown unskew_out {self.algorithm.unskew_out!r}")
        perm = getattr(a_h, "row_block_perm", None)
        if perm:
            bs = a_h.block_size
            inv = a_h.inv_row_perm()   # cached on the handle
            c = c.reshape(len(perm), bs, -1)[inv].reshape(c.shape)
        cperm = getattr(b_h, "col_block_perm", None)
        if cperm:
            # a cols-balanced RIGHT operand permutes C's column blocks
            # (C = A (B P) = (A B) P); invert before the crop
            bs = b_h.block_size
            inv = b_h.inv_col_perm()
            c = c.reshape(c.shape[0], len(cperm), bs)[:, inv]
            c = c.reshape(c.shape[0], -1)
        return c[:a_h.logical_shape[0], :b_h.logical_shape[1]]

    # ------------------------------------------------------------- analysis
    def validate(self, mode: str = "fast", a=None, b=None) -> None:
        """Statically verify this plan (see DESIGN.md "Static analysis").

        ``mode="fast"`` runs the host-side schedule checker over the
        plan's metadata (ppermute bijections, steal3d exactly-once +
        conservation, packed-wire consume-map contracts, sparse pair
        lists, balance perms).  ``mode="full"`` additionally traces the
        executable and runs the jaxpr lint (sort/scatter-free scan
        steps, collective count vs the cost model, overlap-carry
        happens-before).  Raises
        :class:`repro.analysis.PlanValidationError` on any finding.

        Results are memoized per plan and mode, so validating a cached
        plan is a set lookup — ``plan_matmul(validate="fast")`` on a
        warm cache costs nothing.
        """
        if mode == "off":
            return
        if mode not in ("fast", "full"):
            raise ValueError(
                f"unknown validate mode {mode!r} "
                "(expected 'off', 'fast' or 'full')")
        if mode in self._validated:
            return
        from repro import analysis as _analysis
        with _obs.span("plan_build.validate", mode=mode,
                       algorithm=self.algorithm.name):
            findings = _analysis.check_plan(self, a, b)
            if mode == "full" and not findings:
                findings = _analysis.lint_plan(self, a, b)
            if findings:
                raise _analysis.PlanValidationError(findings)
        self._validated.add(mode)
        if mode == "full":
            self._validated.add("fast")   # full subsumes fast

    def cost_model(self, a: Optional[DistBSR] = None) -> Dict[str, float]:
        """Per-step volume / flops of one plan execution (per device).

        Flop counts are the *executed* (padding and coverage included) MXU
        work, the quantity the static scheduler balances.  Pass the sparse
        left-hand handle to also get the paper's Fig-1 per-stage vs
        end-to-end imbalance from its tile counts (feeds
        ``core/schedule.py``).
        """
        if self.steal is not None:
            # structure-true cost precomputed by the steal3d planner
            # (makespan flops + gather/moved/reduce traffic)
            out = dict(self.steal.cost)
        else:
            out = _cost_model(self.algorithm, self.geom, self._a_key,
                              self._b_key, symbolic=self.symbolic,
                              wire_caps=self._wire_caps)
        if isinstance(a, DistBSR):
            per_stage, end_to_end = _schedule.stage_imbalance(
                np.asarray(a.counts, dtype=np.float64))
            out["per_stage_imbalance"] = per_stage
            out["end_to_end_imbalance"] = end_to_end
        out["duplex"] = float(self.algorithm.duplex)
        out["overlap"] = self.overlap
        return out

    def predicted_cost(self, machine: Optional["_roofline.Machine"] = None
                       ) -> float:
        """Predicted seconds per execution (the ``algorithm="auto"`` score)."""
        machine = machine or _roofline.TPU_V5E
        return _predicted_time(self.cost_model(), self.algorithm, machine,
                               self.overlap)

    def predicted_perf(self, machine: "_roofline.Machine") -> Dict[str, float]:
        """Paper SS4 inter-node roofline prediction for this plan.

        Besides the roofline point, includes the alpha-beta-gamma time
        breakdown under this plan's overlap mode: ``t_comp``, ``t_comm``,
        ``t_comm_exposed`` (comm left over after hiding
        ``overlap_eff * t_comp`` of it), and ``predicted_s``.
        """
        cm = self.cost_model()
        peak = _roofline.local_peak(cm["ai_local"], machine)
        return {
            "perf": _roofline.internode_roofline(cm["ai_net"],
                                                 cm["ai_local"], machine),
            "local_peak": peak,
            "net_bound": cm["ai_net"] * machine.net_bw < peak,
            **_time_breakdown(cm, self.algorithm, machine, self.overlap),
            **cm,
        }


# ---------------------------------------------------------------------------
# Operand coercion + plan cache + public entry points
# ---------------------------------------------------------------------------
def _compensate_rhs(b_h: DistMatrix, perm: Tuple[int, ...],
                    block_size: int) -> DistMatrix:
    """Undo a cols-balanced left operand on the right operand's row blocks.

    A ``balance="cols"`` left operand stores ``A' = A P`` (column blocks
    permuted), which permutes the contraction dimension; multiplying by
    ``B' = P^T B`` (row blocks gathered by the same permutation) restores
    ``A' B' = A B``, so the output needs no fix-up — the ROADMAP's "invert
    on B instead".  The compensated handle is cached on the right operand,
    keyed by the permutation, so repeated plans/calls reuse one transform
    (and one abstract key).
    """
    cache = getattr(b_h, "_col_compensated", None)
    if cache is None:
        cache = b_h._col_compensated = {}
    if getattr(b_h, "_compensated_for", None) == perm:
        return b_h                       # already the compensated handle
    got = cache.get(perm)
    if got is not None:
        return got
    perm_arr = np.asarray(perm)
    if isinstance(b_h, DistDense):
        data = b_h.data
        nbr = data.shape[0] // block_size
        data = data.reshape(nbr, block_size, -1)[jnp.asarray(perm_arr)]
        new = DistDense(data.reshape(b_h.shape), b_h.g,
                        logical_shape=b_h.logical_shape)
    else:
        # sparse right operand: host-side dense round trip (construction
        # time, like from_tiled re-balancing), preserving any carried
        # column permutation of B itself (the epilogue inverts it on C)
        t = b_h.tiled
        d = np.asarray(t.to_dense())
        nbr = d.shape[0] // block_size
        d = d.reshape(nbr, block_size, -1)[perm_arr].reshape(d.shape)
        m, n = t.logical_shape or t.shape
        newt = TiledBSR.from_dense(d, ProcessGrid(*t.grid_shape),
                                   t.block_size, capacity="bucket",
                                   dtype=t.dtype)
        newt = dataclasses.replace(newt, logical_shape=(m, n),
                                   col_block_perm=t.col_block_perm)
        new = DistBSR(newt)
    new._compensated_for = perm          # idempotence marker (re-coercion)
    cache[perm] = new
    return new


def _coerce_pair(a, b, *, g: Optional[int] = None, allow_pad: bool = False
                 ) -> Tuple[DistMatrix, DistMatrix]:
    if isinstance(a, DistMatrix):
        a_h = a
    elif isinstance(a, TiledBSR):
        a_h = DistBSR.from_tiled(a)
    else:
        arr = jnp.asarray(a)
        if g is None:
            raise ValueError(
                "a dense left operand needs g=<grid size> or a DistDense "
                "handle (DistDense.from_global)")
        a_h = DistDense.from_global(arr, g)
    if g is not None and a_h.g != g:
        raise ValueError(f"left operand lives on a {a_h.g}x{a_h.g} grid, "
                         f"but g={g} was requested")

    if isinstance(b, DistMatrix):
        b_h = b
    elif isinstance(b, TiledBSR):
        b_h = DistBSR.from_tiled(b)
    else:
        b_h = DistDense.for_rhs(jnp.asarray(b), a_h, allow_pad=allow_pad)

    if getattr(b_h, "row_block_perm", None):
        raise ValueError(
            "the right operand carries a balance='rows' row-block "
            "permutation, which would permute the contraction dimension; "
            "balanced matrices may only be the left operand (the epilogue "
            "inverts the permutation on output rows)")
    if isinstance(a_h, DistDense) and isinstance(b_h, DistBSR):
        raise NotImplementedError(
            "dense x sparse is not supported; compute the transposed "
            "product sparse x dense instead (B^T A^T = (AB)^T)")
    if a_h.g != b_h.g:
        raise ValueError(f"operands on different process grids: "
                         f"{a_h.g}x{a_h.g} vs {b_h.g}x{b_h.g}")
    if a_h.shape[1] != b_h.shape[0]:
        raise ValueError(
            f"inner (padded) dimensions disagree: A is {a_h.shape}, B is "
            f"{b_h.shape}; build the right operand with "
            "DistDense.for_rhs(b, a) to match A's padding")
    cperm = getattr(a_h, "col_block_perm", None)
    if cperm:
        # cols-balanced left operand: permute B's row blocks to compensate
        b_h = _compensate_rhs(b_h, cperm, a_h.block_size)
    return a_h, b_h


def _geometry(a_h: DistMatrix, b_h: DistMatrix, *, impl: Optional[str],
              axis_row: str, axis_col: str, c_store: int = 0,
              overlap: bool = False) -> _Geom:
    a_bsr = isinstance(a_h, DistBSR)
    b_bsr = isinstance(b_h, DistBSR)
    return _Geom(
        g=a_h.g, tm=a_h.tile_shape[0], tn=b_h.tile_shape[1],
        a_nbr=(a_h.tile_shape[0] // a_h.block_size) if a_bsr else 0,
        b_nbr=(b_h.tile_shape[0] // b_h.block_size) if b_bsr else 0,
        b_nbc=(b_h.tile_shape[1] // b_h.block_size) if b_bsr else 0,
        impl=impl, axr=axis_row, axc=axis_col,
        out_dtype=jnp.promote_types(a_h.dtype, b_h.dtype), c_store=c_store,
        overlap=overlap)


def _symbolic_for(a_h: DistBSR, b_h: DistBSR) -> "SymbolicProduct":
    """Memoized symbolic phase, keyed on the operands' structures."""
    key = (a_h.structure_key(), b_h.structure_key())
    sym = _SYMBOLIC_CACHE.get(key)
    if sym is None:
        with _obs.span("plan_build.symbolic"):
            sym = _symbolic.symbolic_spgemm(a_h.tiled, b_h.tiled)
        _SYMBOLIC_CACHE[key] = sym
    return sym


def _predicted_density_for(a_h: DistBSR, b_h: DistBSR) -> float:
    """Memoized structure-only density (the output="auto" decision input)."""
    key = (a_h.structure_key(), b_h.structure_key())
    sym = _SYMBOLIC_CACHE.get(key)
    if sym is not None:
        return sym.density()
    d = _DENSITY_CACHE.get(key)
    if d is None:
        d = _symbolic.predicted_density(a_h.tiled, b_h.tiled)
        _DENSITY_CACHE[key] = d
    return d


def _sparse_output_eligible(a_h: DistMatrix, b_h: DistMatrix) -> Optional[str]:
    """None when output="sparse" can serve these operands, else the reason."""
    if not (isinstance(a_h, DistBSR) and isinstance(b_h, DistBSR)):
        return "sparse output needs two block-sparse (DistBSR) operands"
    if a_h.block_size != b_h.block_size:
        return (f"sparse output needs equal block sizes, got "
                f"{a_h.block_size} and {b_h.block_size}")
    for h, who in ((a_h, "left"), (b_h, "right")):
        if getattr(h, "row_block_perm", None) or \
                getattr(h, "col_block_perm", None):
            return (
                f"sparse output does not support balanced operands: the "
                f"{who} operand carries a balance permutation, which the "
                "symbolic phase cannot compose into its pair lists yet; "
                'either keep a dense output for this multiply '
                '(output="dense") or rebuild the operand without balancing '
                '(balance="none")')
    return None


def _mesh_key(mesh):
    try:
        hash(mesh)
        return mesh
    except TypeError:
        return id(mesh)


def _resolve_overlap(overlap: str) -> str:
    """Validate the ``overlap=`` request ("auto" | "on" | "off").

    ``"auto"`` (default) builds the split-step double-buffered bodies for
    the scanned schedules (steal3d's segment split stays opt-in — see
    :func:`plan_matmul`) and
    scores schedules with the legacy per-style overlap preference;
    ``"on"`` additionally credits the fitted ``machine.overlap_eff`` to
    every non-amortized schedule when scoring; ``"off"`` builds the
    bulk-synchronous bodies and serializes comm in every score (the A/B
    baseline ``benchmarks/overlap_bench.py`` measures against).
    """
    if overlap not in ("auto", "on", "off"):
        raise ValueError(f"unknown overlap {overlap!r}; one of "
                         "('auto', 'on', 'off')")
    return overlap


def _resolve_wire(wire: str, output: str) -> str:
    """Resolve the ``wire=`` request ("auto" | "padded" | "packed").

    ``"auto"`` keeps today's behaviour for dense-output plans (padded
    wire, so structurally different operands with equal abstract shapes
    keep sharing one cached plan) and resolves to ``"packed"`` for
    sparse-output plans, which are specialized to the operands' structure
    anyway — there packing is a strict win.
    """
    if wire not in ("auto", "padded", "packed"):
        raise ValueError(f"unknown wire {wire!r}; one of "
                         "('auto', 'padded', 'packed')")
    if wire == "auto":
        return "packed" if output == "sparse" else "padded"
    return wire


def _wire_caps_for(a_h: DistMatrix, b_h: DistMatrix,
                   packable: Tuple[str, ...]) -> Dict[str, int]:
    """Estimated packed wire capacities from the handles' stored counts.

    ``counts`` bounds the data-real block count from above (a chained
    sparse-output handle may store structurally-predicted blocks that are
    numerically zero), so scoring stays devices-free while actual plans
    pack against the exact structure.
    """
    caps = {}
    for who, h in (("a", a_h), ("b", b_h)):
        if who in packable and isinstance(h, DistBSR):
            counts = np.asarray(h.counts)
            caps[who] = wire_capacity(
                int(counts.max()) if counts.size else 0,
                h.tiled.store_capacity)
    return caps


def _b_pack_wins(b_h: DistMatrix) -> bool:
    """Whether packing B beats the densified tile on a dense-output path.

    Packed A always wins (wire capacity <= stored stride, and the
    rows/cols index traffic stays home), but a dense-output body consumes
    B as a dense tile either way — so shipping B packed only pays when
    its real blocks cover less than the tile: near-block-dense operands
    keep riding densified.  Decided on stored ``counts`` (an upper bound
    on real blocks), so a win is never claimed that packing can't keep.
    """
    if not isinstance(b_h, DistBSR):
        return False
    counts = np.asarray(b_h.counts)
    wc = wire_capacity(int(counts.max()) if counts.size else 0,
                       b_h.tiled.store_capacity)
    bs = b_h.block_size
    tm, tn = b_h.tile_shape
    return wc * bs * bs < tm * tn


def auto_select(a, b, *, machine: Optional["_roofline.Machine"] = None,
                g: Optional[int] = None, allow_pad: bool = False,
                axis_row: str = "row", axis_col: str = "col",
                registry: Optional[AlgorithmRegistry] = None,
                output: str = "dense", wire: str = "auto",
                overlap: str = "auto", _symbolic=None
                ) -> Tuple[str, Dict[str, float]]:
    """Score every registered schedule for ``a @ b``; pick the cheapest.

    Returns ``(name, scores)`` where ``scores`` maps every algorithm to its
    predicted seconds (:func:`_predicted_time` on its cost model).  Pure
    planning — no mesh or devices needed, so large grids can be scored on
    a single host.  Ties resolve to registration order.

    ``output="sparse"`` scores only the schedules with a sparse-output
    body, against the symbolic-phase cost model: B rides in stored block
    form and C is charged at its *actual* packed size, so the ranking can
    differ from the dense-output one for the same operands.

    ``wire="packed"`` scores every schedule against its *packed* wire
    terms (each schedule's packable operands at their wire capacities;
    steal3d's packed gather/moved/reduce rounds), so the choice flips
    where shipping only real blocks changes the comm/compute trade.

    ``overlap`` feeds the cost model's comm-hiding term (see
    :func:`_overlap_eff`): ``"on"`` credits the machine's fitted
    ``overlap_eff`` to every non-amortized schedule, so with a fitted
    machine the choice can flip toward a schedule whose comm hides
    under its compute.
    """
    a_h, b_h = _coerce_pair(a, b, g=g, allow_pad=allow_pad)
    machine = machine or _roofline.TPU_V5E
    registry = registry or REGISTRY
    wire = _resolve_wire(wire, output)
    overlap = _resolve_overlap(overlap)
    if wire == "packed" and not (isinstance(a_h, DistBSR)
                                 or isinstance(b_h, DistBSR)):
        raise ValueError(
            "wire='packed' needs at least one block-sparse (DistBSR) "
            "operand — dense operands have no packable structure; use "
            "wire='padded'")
    sym = None
    candidates = list(registry)
    if output == "sparse":
        reason = _sparse_output_eligible(a_h, b_h)
        if reason:
            raise ValueError(reason)
        sym = _symbolic if _symbolic is not None else _symbolic_for(a_h, b_h)
        candidates = [alg for alg in candidates
                      if alg.sparse_body is not None]
    # geom.overlap here only reaches the steal3d planner cache (cost
    # scoring never reads it); match plan_matmul's opt-in rule so the
    # scoring build is the one a steal3d win then reuses.
    geom = _geometry(a_h, b_h, impl=None, axis_row=axis_row,
                     axis_col=axis_col,
                     c_store=sym.store_capacity if sym else 0,
                     overlap=overlap == "on")
    a_key, b_key = a_h.abstract_key(), b_h.abstract_key()
    scores = {}
    for alg in candidates:
        if alg.cost_fn is not None:       # structure-dependent (steal3d)
            cm = alg.cost_fn(alg, geom, a_h, b_h, wire=wire)
        else:
            caps = None
            if wire == "packed":
                packable = ("a", "b") if sym is not None else alg.packable
                caps = _wire_caps_for(a_h, b_h, packable)
                if sym is None and "b" in caps and not _b_pack_wins(b_h):
                    del caps["b"]
            cm = _cost_model(alg, geom, a_key, b_key, symbolic=sym,
                             wire_caps=caps)
        scores[alg.name] = _predicted_time(cm, alg, machine, overlap)
    if not scores:
        raise ValueError("no algorithms registered" if output != "sparse"
                         else "no sparse-output algorithms registered")
    return min(scores, key=scores.get), scores


# output="auto" emits a sparse DistBSR when the symbolic phase predicts C's
# block density at or below this threshold; above it, the packed form loses
# its footprint advantage and scatter overhead dominates the dense MXU path.
SPARSE_OUTPUT_DENSITY_THRESHOLD = 0.25


def _plan_matmul_impl(a, b, *, algorithm: str = "ring_c", mesh=None,
                impl: Optional[str] = None, g: Optional[int] = None,
                axis_row: str = "row", axis_col: str = "col",
                allow_pad: bool = False, cache: bool = True,
                machine: Optional["_roofline.Machine"] = None,
                output: str = "dense",
                sparse_threshold: Optional[float] = None,
                wire: str = "auto", overlap: str = "auto",
                validate: str = "off", assignment=None) -> MatmulPlan:
    """Build (or fetch from the shared cache) a plan for ``a @ b``.

    ``a`` / ``b`` may be :class:`DistMatrix` handles (preferred — placement
    caches live on the handle), raw :class:`TiledBSR` values, or plain dense
    arrays (``g`` required when both are dense).  ``cache=False`` forces a
    fresh plan — i.e. the legacy per-call behaviour, retracing every time.

    ``algorithm="auto"`` scores every registered schedule with
    :func:`auto_select` (against ``machine``, default TPU v5e) and builds
    the min-predicted-cost one; the choice and all candidate scores are
    recorded on the plan (``plan.requested``, ``plan.auto_scores``).

    ``output`` selects the SpGEMM output representation: ``"dense"`` (the
    default — the plan returns a cropped dense array), ``"sparse"`` (two
    DistBSR operands only; the symbolic phase predicts C's block structure,
    the numeric phase accumulates straight into packed blocks, and the plan
    returns a :class:`DistBSR` that chains into further multiplies without
    a densify/re-tile round trip), or ``"auto"`` (sparse when the predicted
    output block density is at or below ``sparse_threshold``, default
    :data:`SPARSE_OUTPUT_DENSITY_THRESHOLD`).  Sparse-output plans are
    specialized to the operands' sparsity *structure* (not values), which
    joins the cache key.

    ``wire`` selects the communication layout: ``"padded"`` ships sparse
    tiles at their stored ``store_capacity`` stride, ``"packed"`` ships
    only real blocks (``repro.core.wire``: blocks-only buffers at the
    bucketed wire capacity, consume maps stay home) on every path the
    schedule supports, and ``"auto"`` (default) packs sparse-output plans
    — already structure-specialized, so packing there is a strict win —
    while keeping dense-output plans padded so structurally different
    operands with equal abstract shapes keep sharing one cached plan.
    Packed plans join the cache keyed on the packed operands' structure
    fingerprints; a schedule with no packable traffic for these operands
    (e.g. ``ring_a`` with a dense B) degrades to its padded plan.

    ``overlap`` selects the schedule bodies' dependence structure:
    ``"auto"`` (default) and ``"on"`` build the split-step
    double-buffered bodies — step t+1's collective issues *before* step
    t's accumulate, carrying a two-slot buffer per stream, so the
    compiler/runtime can fly transfers under compute — while ``"off"``
    builds the bulk-synchronous bodies (the measurement baseline).
    Exception: steal3d's own/stolen segment split costs an extra kernel
    dispatch, so ``"auto"`` keeps its bulk single-segment plan and only
    explicit ``"on"`` splits it.  The mode also feeds auto-selection's
    comm-hiding credit (see :func:`auto_select`) and joins the cache
    key.

    ``validate`` statically verifies the plan before handing it back
    (see DESIGN.md "Static analysis"): ``"off"`` (default) skips,
    ``"fast"`` runs the host-side schedule checker (ppermute bijections,
    steal3d exactly-once, packed consume-map contracts, sparse pair
    lists, balance perms), ``"full"`` additionally traces the executable
    and runs the jaxpr lint.  Verification is memoized per plan, so a
    cache hit revalidates for free; any finding raises
    :class:`repro.analysis.PlanValidationError` with named rule ids.

    ``assignment`` injects a prebuilt :class:`repro.core.schedule.Assignment3D`
    into a static-planner schedule (steal3d) in place of the plan-time LPT
    — the elastic-recovery path, where the assignment was rebuilt for a
    surviving mesh.  It requires an explicit static-planner ``algorithm``
    (not ``"auto"``), runs ``validate_assignment``'s fail-fast invariant
    checks inside ``build_steal_plan``, and bypasses the plan cache in
    both directions (an injected plan is never shared).
    """
    if validate not in ("off", "fast", "full"):
        raise ValueError(f"unknown validate {validate!r}; one of "
                         "('off', 'fast', 'full')")
    if assignment is not None:
        if algorithm == "auto" \
                or REGISTRY.get(algorithm).static_planner is None:
            raise ValueError(
                "assignment= requires an explicit algorithm with a static "
                "planner (steal3d); "
                f"got algorithm={algorithm!r}")
        cache = False
    a_h, b_h = _coerce_pair(a, b, g=g, allow_pad=allow_pad)
    overlap = _resolve_overlap(overlap)
    if output not in ("dense", "sparse", "auto"):
        raise ValueError(f"unknown output {output!r}; one of "
                         "('dense', 'sparse', 'auto')")
    if output == "sparse":
        reason = _sparse_output_eligible(a_h, b_h)
        if reason:
            raise ValueError(reason)
    elif output == "auto":
        if sparse_threshold is None:
            sparse_threshold = SPARSE_OUTPUT_DENSITY_THRESHOLD
        alg_can_sparse = algorithm == "auto" or \
            REGISTRY.get(algorithm).sparse_body is not None
        if alg_can_sparse and _sparse_output_eligible(a_h, b_h) is None \
                and _predicted_density_for(a_h, b_h) <= sparse_threshold:
            output = "sparse"
        else:
            output = "dense"
    requested = algorithm
    auto_scores = None
    wire = _resolve_wire(wire, output)
    if wire == "packed" and not (isinstance(a_h, DistBSR)
                                 or isinstance(b_h, DistBSR)):
        raise ValueError(
            "wire='packed' needs at least one block-sparse (DistBSR) "
            "operand — dense operands have no packable structure; use "
            "wire='padded'")
    sym = _symbolic_for(a_h, b_h) if output == "sparse" else None
    if algorithm == "auto":
        with _obs.span("plan_build.auto_select"):
            algorithm, auto_scores = auto_select(
                a_h, b_h, machine=machine, axis_row=axis_row,
                axis_col=axis_col, allow_pad=allow_pad, output=output,
                wire=wire, overlap=overlap, _symbolic=sym)
    alg = REGISTRY.get(algorithm)
    if sym is not None and alg.sparse_body is None:
        raise ValueError(
            f"algorithm {algorithm!r} has no sparse-output body; one of "
            f"{sparse_algorithms()} (or use output='dense')")
    # which operands actually ship packed on this plan (a schedule with no
    # packable traffic for these operands degrades to its padded plan)
    packs: Tuple[str, ...] = ()
    if wire == "packed":
        if sym is not None:
            packs = ("a", "b")
        elif alg.static_planner is not None:
            # static planners pack the A side only (declared via packable)
            packs = ("a",) if "a" in alg.packable \
                and isinstance(a_h, DistBSR) else ()
        elif alg.packed_body is not None:
            packs = tuple(
                t for t in alg.packable
                if isinstance(a_h if t == "a" else b_h, DistBSR))
            if "b" in packs and not _b_pack_wins(b_h):
                # a near-block-dense B is cheaper densified than packed;
                # keep it riding as a dense tile (see _b_pack_wins)
                packs = tuple(t for t in packs if t != "b")
        if not packs:
            wire = "padded"
    mesh = _prep_mesh(mesh, a_h.g, axis_row, axis_col)
    key = (alg.name, impl, axis_row, axis_col, allow_pad, overlap,
           _mesh_key(mesh), a_h.abstract_key(), b_h.abstract_key())
    if sym is not None:
        # pair lists are baked into the executable, so the structure is
        # part of the plan's identity, not just its abstract shapes
        key += ("sparse", a_h.structure_key(), b_h.structure_key())
    if alg.static_planner is not None:
        # the LPT assignment (and therefore the executable's pair lists
        # and rounds) is a function of A's sparsity structure
        key += ("steal", a_h.structure_key()
                if isinstance(a_h, DistBSR) else None)
    if wire == "packed":
        # consume maps / remapped pair lists are baked per structure
        key += ("wire-packed",) + tuple(
            (a_h if t == "a" else b_h).structure_key() for t in packs)
    if cache:
        plan = _PLAN_CACHE.get(key)
        if plan is not None:
            if auto_scores is not None and plan.auto_scores is None:
                plan.auto_scores = auto_scores   # record for introspection
            plan.validate(validate, a_h, b_h)
            return plan
    # Scanned schedules double-buffer on "auto" (the split is a pure
    # scan reordering — free).  steal3d's own/stolen segment split costs
    # a second kernel dispatch, which only pays for itself when the
    # stolen-tile transfers are genuinely asynchronous — so it is
    # opt-in: explicit overlap="on" only.
    body_overlap = (overlap == "on") if alg.static_planner is not None \
        else (overlap != "off")
    geom = _geometry(a_h, b_h, impl=impl, axis_row=axis_row,
                     axis_col=axis_col,
                     c_store=sym.store_capacity if sym else 0,
                     overlap=body_overlap)
    steal = alg.static_planner(a_h, b_h, geom, wire=wire,
                               assignment=assignment) \
        if alg.static_planner is not None else None
    wire_aux = wire_caps = wire_fps = None
    if wire == "packed" and steal is None:
        with _obs.span("plan_build.wire", packs="".join(packs)):
            a_po = a_h.packed_operand() if "a" in packs else None
            b_po = b_h.packed_operand() if "b" in packs else None
            wire_caps = {t: po.wire_capacity for t, po in
                         (("a", a_po), ("b", b_po)) if po is not None}
            wire_fps = {t: po.fingerprint for t, po in
                        (("a", a_po), ("b", b_po)) if po is not None}
            if sym is not None:
                # compose the stored->packed slot maps into the pair lists
                wire_aux = {
                    "pa": _wire.remap_pairs_packed(sym.pair_a, a_po, "a"),
                    "pb": _wire.remap_pairs_packed(sym.pair_b, b_po, "b"),
                }
            else:
                wire_aux = alg.wire_planner(a_po, b_po, geom)
    elif steal is not None and steal.wire == "packed":
        wire_caps = {"a": steal.a_wire_capacity}
    with _obs.span("plan_build.executable", algorithm=alg.name):
        plan = MatmulPlan(alg, geom,
                          mesh, a_h.abstract_key(), b_h.abstract_key(),
                          allow_pad=allow_pad, requested=requested,
                          auto_scores=auto_scores, symbolic=sym,
                          steal=steal, wire=wire, packs=packs,
                          wire_aux=wire_aux, wire_caps=wire_caps,
                          wire_fps=wire_fps, overlap=overlap)
    plan.validate(validate, a_h, b_h)
    if cache:
        _PLAN_CACHE[key] = plan
    return plan


def plan_matmul(a, b, **kw) -> MatmulPlan:
    sp = _obs.span("plan_build",
                   algorithm=str(kw.get("algorithm", "ring_c")),
                   output=str(kw.get("output", "dense")),
                   wire=str(kw.get("wire", "auto")),
                   overlap=str(kw.get("overlap", "auto")))
    hits0 = _PLAN_CACHE.hits
    with sp:
        plan = _plan_matmul_impl(a, b, **kw)
        sp.note(algorithm=plan.algorithm.name, wire=plan.wire,
                output=plan.output, cached=_PLAN_CACHE.hits > hits0)
    return plan


plan_matmul.__doc__ = _plan_matmul_impl.__doc__


def matmul(a, b, *, algorithm: str = "ring_c", mesh=None,
           impl: Optional[str] = None, g: Optional[int] = None,
           axis_row: str = "row", axis_col: str = "col",
           allow_pad: bool = False,
           machine: Optional["_roofline.Machine"] = None,
           output: str = "dense",
           sparse_threshold: Optional[float] = None,
           wire: str = "auto", overlap: str = "auto"):
    """Polymorphic distributed ``a @ b``.

    Dispatches sparse x dense -> SpMM, sparse x sparse -> SpGEMM, and
    dense x dense -> the dense engine, all through the shared plan cache:
    repeated calls with the same abstract shapes never re-trace.
    ``algorithm="auto"`` cost-model-selects the schedule and
    ``output="sparse"|"auto"`` returns a :class:`DistBSR` for sparse
    products, so chained multiplies ``matmul(matmul(a, a), a)`` stay packed
    end to end (see :func:`plan_matmul`).
    """
    a_h, b_h = _coerce_pair(a, b, g=g, allow_pad=allow_pad)
    plan = plan_matmul(a_h, b_h, algorithm=algorithm, mesh=mesh, impl=impl,
                       axis_row=axis_row, axis_col=axis_col,
                       allow_pad=allow_pad, machine=machine, output=output,
                       sparse_threshold=sparse_threshold, wire=wire,
                       overlap=overlap)
    return plan(a_h, b_h)
