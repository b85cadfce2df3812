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
  geometry (``schedules._Geom``), operand pack specs and placement
  requirements, and holds one jit-compiled ``shard_map`` executable:
  calling the plan again with the same abstract shapes never re-traces.  ``plan.cost_model()`` exposes the
  per-step network volume / flops that feed ``core/roofline.py`` and
  ``core/schedule.py``.
* :func:`matmul` — one polymorphic entry point dispatching
  sparse x dense -> SpMM, sparse x sparse -> SpGEMM and dense x dense ->
  the dense engine through :data:`REGISTRY` (an :class:`AlgorithmRegistry`).
  Algorithms register declaratively with their required operand placements,
  output unskew and per-step wire traffic, so new schedules (work-stealing
  layouts, stationary-B, ...) plug in without touching the engine.

The algorithm family (``summa_bcast``, ``summa_ag``, ``ring_c``,
``ring_a``, ``ring_c_bidir``, ``steal3d``) and its shard_map bodies live
in :mod:`repro.core.schedules`.  ``plan_matmul(..., algorithm="auto")``
scores every registered schedule with the alpha-beta-gamma cost model
(:func:`auto_select`) and builds the cheapest — the static analogue of
Bharadwaj et al.'s observation that the best distributed sparse schedule
flips with sparsity and aspect ratio.

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
from jax.sharding import PartitionSpec as P

from .. import obs as _obs
from ..kernels.bsr_spmm import pair_grid_steps, spmm_block_n
from . import entries as _entries
from . import roofline as _roofline
from . import schedule as _schedule
from . import schedules as _schedules
from . import steal3d as _steal3d
from . import symbolic as _symbolic
from . import wire as _wire
from .bsr import TiledBSR
from .dist import make_grid_mesh, tile_mesh, unskew_c_rows
from .grid import ProcessGrid, bucket_capacity, ceil_div, pad_to_multiple
from .schedules import _Geom
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
            invalidate_plans(algorithm=alg.name)
        self._algorithms[alg.name] = alg
        return alg

    def unregister(self, name: str) -> None:
        if self._algorithms.pop(name, None) is not None:
            invalidate_plans(algorithm=name)

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


def register_algorithm(name: str, *,
                       registry: AlgorithmRegistry = REGISTRY, **fields):
    """Decorator registering a shard_map body as a named algorithm;
    ``fields`` are :class:`Algorithm`'s (its defaults hold for the rest,
    and a field it lacks raises ``TypeError``)."""
    def deco(body):
        registry.register(Algorithm(name=name, body=body, **fields))
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


# ---- per-schedule wire planners (consume-map construction) ----------------
def _summa_bases(g: int, wc: int) -> np.ndarray:
    """Flat base offset of inner step k's tile in an all-gathered pool."""
    return np.broadcast_to(np.arange(g, dtype=np.int64) * wc, (g, g, g))


def _wire_planner(tiles_a, tiles_b, pooled: bool = False,
                  tiles_a_bwd=None) -> Callable:
    """A schedule's wire planner: the consume maps of a packed A whose
    tile at each step is ``tiles_a(g)`` (and ``tiles_a_bwd(g)`` on the -1
    ring of a bidirectional schedule) and the densify maps of a packed B
    (``tiles_b(g)``); ``pooled`` steps index one all-gathered pool."""
    def plan(a_po, b_po, geom: _Geom) -> Dict[str, np.ndarray]:
        g = geom.g

        def bases(po):
            return _summa_bases(g, po.wire_capacity) if pooled else None

        aux: Dict[str, np.ndarray] = {}
        for tiles, sfx in ((tiles_a, ""), (tiles_a_bwd, "_bwd")):
            if a_po is not None and tiles is not None:
                cons = _wire.schedule_consume(a_po, tiles(g), bases(a_po))
                aux.update({f"a_{k}{sfx}": cons[k]
                            for k in ("gidx", "rows", "cols")})
        if b_po is not None:
            aux["b_dmap"] = _wire.schedule_dense_map(b_po, tiles_b(g),
                                                     bases(b_po))
        return aux
    return plan


# ---------------------------------------------------------------------------
# The registered schedules (bodies: repro.core.schedules)
# ---------------------------------------------------------------------------
register_algorithm("summa_bcast", style="bsp",
                   sparse_body=_schedules._sparse_body_summa_bcast,
                   packed_body=_schedules._packed_body_summa_bcast,
                   packable=("a", "b"),
                   wire_planner=_wire_planner(_wire.tiles_summa_a,
                                              _wire.tiles_summa_b),
                   k_order=lambda i, j, t, g: t + 0 * (i + j),
                   )(_schedules._body_summa_bcast)
register_algorithm("summa_ag", style="bsp", wire_amortized=True,
                   sparse_body=_schedules._sparse_body_summa_ag,
                   packed_body=_schedules._packed_body_summa_ag,
                   packable=("a", "b"),
                   wire_planner=_wire_planner(_wire.tiles_summa_a,
                                              _wire.tiles_summa_b,
                                              pooled=True),
                   k_order=lambda i, j, t, g: t + 0 * (i + j),
                   )(_schedules._body_summa_ag)
register_algorithm("ring_c", a_placement=SKEW_ROWS, b_placement=SKEW_COLS,
                   sparse_body=_schedules._sparse_body_ring_c,
                   packed_body=_schedules._packed_body_ring_c,
                   packable=("a", "b"),
                   wire_planner=_wire_planner(_wire.tiles_ring_c,
                                              _wire.tiles_ring_c_b),
                   k_order=lambda i, j, t, g: (i + j + t) % g,
                   )(_schedules._body_ring_c)
register_algorithm("ring_a", b_placement=STATIONARY_A, unskew_out="rows",
                   wire=("b", "c"), balance_axis="cols",
                   packed_body=_schedules._packed_body_ring_a,
                   packable=("b",),
                   wire_planner=_wire_planner(None, _wire.tiles_ring_a_b),
                   )(_schedules._body_ring_a)
register_algorithm("ring_c_bidir", a_placement=SKEW_ROWS,
                   b_placement=SKEW_COLS, wire=("a", "a", "b"), duplex=2,
                   packed_body=_schedules._packed_body_ring_c_bidir,
                   packable=("a",),
                   wire_planner=_wire_planner(
                       _wire.tiles_ring_c, None,
                       tiles_a_bwd=_wire.tiles_ring_c_bwd),
                   msgs_per_step=4,   # a_fwd, a_bwd, b_left, b_right
                   )(_schedules._body_ring_c_bidir)


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

register_algorithm("steal3d", style="bsp", wire=("a", "b", "c"),
                   static_planner=_steal_plan_for, cost_fn=_steal3d_cost,
                   packable=("a",))(_schedules._body_steal3d)


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


def _tile_grid(x):
    """The mesh that holds ``x`` one tile per device: ``x`` is split over
    both axes of a two-axis mesh in its two leading dimensions and over
    nothing else.  ``None`` for an array laid out otherwise."""
    s = getattr(x, "sharding", None)
    if not isinstance(s, jax.sharding.NamedSharding) \
            or len(s.mesh.axis_names) != 2:
        return None
    spec = tuple(s.spec) + (None,) * (x.ndim - len(s.spec))
    if spec[:2] != tuple(s.mesh.axis_names) \
            or any(p is not None for p in spec[2:]):
        return None
    return s.mesh


def _map_tiles(x, fn):
    """An array laid out as ``x`` (one tile per device of its mesh) whose
    tile at mesh position (i, j) is ``fn((i, j), tiles)``, where ``tiles``
    maps each position to the single-device array of ``x``'s tile there.
    No array is gathered on any device."""
    mesh = x.sharding.mesh
    at = {d: ij for ij, d in np.ndenumerate(mesh.devices)}
    tiles = {at[sh.device]: sh.data for sh in x.addressable_shards}
    out = [fn(ij, tiles) for ij, _ in np.ndenumerate(mesh.devices)]
    g_r, g_c = mesh.devices.shape
    shape = (g_r * out[0].shape[0], g_c * out[0].shape[1]) + out[0].shape[2:]
    return jax.make_array_from_single_device_arrays(shape, x.sharding, out)


def _place_tree(tree: Dict[str, jnp.ndarray], placement: str,
                g: int) -> Dict[str, jnp.ndarray]:
    """``tree`` in ``placement``: mesh position (i, j) holds the natural
    tile :func:`repro.core.wire.placement_tiles` names.  Each tile moves
    straight from the device that holds it to the one that needs it (under
    the span ``handle.place``), so no device holds more than its own tile
    and the one it receives.  A leaf not laid out one tile per device (a
    handle staged on one device) is first committed to
    :func:`repro.core.dist.tile_mesh`; a placement needs its g * g
    devices."""
    if placement == NATURAL:
        return tree
    if not all(_tile_grid(v) is not None for v in tree.values()):
        mesh = tile_mesh(g)
        if mesh is None:
            raise ValueError(f"placement {placement!r} of a {g}x{g} grid "
                             f"needs {g * g} devices, JAX has "
                             f"{len(jax.devices())}")
        tiles = jax.sharding.NamedSharding(mesh, P(*mesh.axis_names))
        tree = {k: v if _tile_grid(v) is not None
                else jax.device_put(v, tiles) for k, v in tree.items()}
    src = _wire.placement_tiles(placement, g)
    mesh_devices = next(iter(tree.values())).sharding.mesh.devices
    moved = 0

    def move(ij, tiles):
        nonlocal moved
        tile = tiles[tuple(src[ij])]
        if tuple(src[ij]) == ij:
            return tile
        moved += tile.nbytes
        return jax.device_put(tile, mesh_devices[ij])

    with _obs.span("handle.place", placement=placement) as sp:
        out = {k: _map_tiles(v, move) for k, v in tree.items()}
        sp.note(bytes_moved=moved)
    return out


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
        self._entry_counts: Optional[np.ndarray] = None
        self._entry_placed: Dict[tuple, Dict[str, jnp.ndarray]] = {}

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
            # pack each natural tile where it lives, then place the packed
            # tiles: only real blocks move between devices
            pidx = self.packed_operand().pack_idx            # [g, g, wc]
            blocks = self.tiled.blocks
            if _tile_grid(blocks) is not None:
                devices = blocks.sharding.mesh.devices
                packed = _map_tiles(blocks, lambda ij, tiles: tiles[ij][
                    :, :, jax.device_put(pidx[ij], devices[ij])])
            else:
                g = self.g
                packed = blocks[jnp.arange(g)[:, None, None],
                                jnp.arange(g)[None, :, None],
                                jnp.asarray(pidx)]
            tree = _place_tree({"blocks": packed}, placement, self.g)
            cache[placement] = tree
        if commit is not None:
            tree = cache[placement] = commit(tree)
        return tree

    def entry_counts(self) -> np.ndarray:
        """Nonzero entries of each tile, host ``int64[g, g]``: entries whose
        bit pattern is not zero (``-0.0`` and NaN count).  Counted once per
        handle, each tile on the device that holds it."""
        if self._entry_counts is None:
            blocks = self.tiled.blocks
            self._entry_counts = _entries.count(blocks, _tile_grid(blocks))
        return self._entry_counts

    def entry_wire(self, placement: str, cap: int,
                   commit: Optional[Callable] = None
                   ) -> Dict[str, jnp.ndarray]:
        """The tiles as their nonzero entries for a placement: ``{"vals":
        [g, g, cap], "idx": int32[g, g, cap]}`` (:mod:`repro.core.entries`).
        Each natural tile is encoded on its own device, then the encodings
        are placed, so only entries move between devices.  Cached per
        placement and ``cap``; ``commit`` as in :meth:`placed`."""
        most = int(self.entry_counts().max())
        if most > cap:
            raise ValueError(
                f"a tile of the left operand holds {most} nonzero entries, "
                f"more than this plan's {cap}; build a new plan with "
                "plan_matmul")
        placement = _canonical_placement(placement, self.g)
        key = (placement, cap)
        tree = self._entry_placed.get(key)
        if tree is None:
            blocks = self.tiled.blocks
            # wait for the encoder before any tile moves: its work space
            # is gone before a device receives a second tile of blocks
            tree = jax.block_until_ready(
                _entries.encode(blocks, _tile_grid(blocks), cap))
            tree = self._entry_placed[key] = _place_tree(tree, placement,
                                                         self.g)
        if commit is not None:
            tree = self._entry_placed[key] = commit(tree)
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
            t = self.tiled
            tree = _place_tree({"blocks": t.blocks, "rows": t.rows,
                                "cols": t.cols}, placement, self.g)
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
        """Wrap a global array, zero-padding each dim to a multiple of g.

        Where :func:`repro.core.dist.tile_mesh` gives a mesh (``g > 1``
        and ``g * g`` devices), tile (i, j) goes straight to device (i, j)
        of it, the layout plans run in; a host array is padded on the host.
        """
        x = x if isinstance(x, jax.Array) else np.asarray(x)
        m, n = x.shape
        rp = pad_to_multiple(m, g) if rows_pad is None else rows_pad
        cp = pad_to_multiple(n, g) if cols_pad is None else cols_pad
        if rp < m or cp < n or rp % g or cp % g:
            raise ValueError(f"bad padded shape ({rp}, {cp}) for array "
                             f"{x.shape} on a {g}x{g} grid")
        if (rp, cp) != (m, n):
            if isinstance(x, jax.Array):
                x = jnp.zeros((rp, cp), x.dtype).at[:m, :n].set(x)
            else:
                x = np.pad(x, ((0, rp - m), (0, cp - n)))
        mesh = tile_mesh(g)
        if mesh is not None:
            x = jax.device_put(x, jax.sharding.NamedSharding(
                mesh, P(*mesh.axis_names)))
        return cls(x, g, logical_shape=(m, n))

    @classmethod
    def for_rhs(cls, x, a: DistMatrix, *, allow_pad: bool = False
                ) -> "DistDense":
        """Wrap the right operand of ``a @ x``, matching a's padded K dim.

        The inner dimension must equal a's logical or padded column count;
        anything smaller is only zero-padded with an explicit
        ``allow_pad=True`` (silent padding hides shape bugs).
        """
        x = x if isinstance(x, jax.Array) else np.asarray(x)
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
            tree = _place_tree({"dense": self.data}, placement, self._g)
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
            # coverage blocks in sorted order like TiledBSR._scan_dense
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


# the unsharded dims of an operand leaf after its (row, col) grid dims:
# blocks [bs, bs] after a slot dim, rows/cols/vals/idx a slot dim
_LEAF_DIMS = {"dense": 0, "blocks": 3}


def _specs_for_keys(keys: Tuple[str, ...], axr: str, axc: str) -> Dict:
    return {k: P(axr, axc, *(None,) * _LEAF_DIMS.get(k, 1)) for k in keys}


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
    tiles, flops_step = _dense_output_tiles(geom, a_key, b_key, wire_caps)
    return _assemble_cost(alg, g, tiles["a"], tiles["b"], tiles["c"],
                          flops_step, tiles)


def _dense_output_tiles(geom: _Geom, a_key: tuple, b_key: tuple,
                        wire_caps: Dict[str, int]
                        ) -> Tuple[Dict[str, float], float]:
    """The bytes of one A, B and C tile as a dense-output body ships it
    (``{"a", "b", "c"}``) and the flops of one step (see
    :func:`_cost_model`)."""
    g = geom.g
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
    return {"a": a_bytes, "b": b_bytes, "c": c_bytes}, flops_step


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
                 overlap: str = "auto", entry_cap: int = 0):
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
        # wire="entries": A's stream ships as this many entries a tile
        self._entry_cap = entry_cap
        self.traces = 0
        # static-verification memo: modes this plan already passed
        # ("fast"/"full") — revalidating a cached plan is a set lookup
        self._validated: set = set()
        a_keys = _tree_keys(a_key) + (("vals", "idx") if entry_cap else ())
        specs = (_specs_for_keys(a_keys, geom.axr, geom.axc),
                 _specs_for_keys(_tree_keys(b_key), geom.axr, geom.axc))
        blocks_spec = {"blocks": P(geom.axr, geom.axc, None, None, None)}
        if steal is not None or (symbolic is None and wire_aux is not None):
            # steal3d and packed-wire dense-output plans: the executable is
            # specialized to plan-time index arrays riding as a third
            # operand tree, committed in their mesh sharding once (like
            # sparse-output pair lists).  steal3d's are the LPT
            # assignment's pair lists, move-round gather indices and
            # reduce-round slot selectors, and only A's block data is
            # sharded in for sparse A.  A packed plan's are the consume
            # maps (augmented-list gathers / densify-by-gather maps built
            # by repro.core.wire), and a packed operand ships blocks-only
            # at the wire capacity.
            aux = steal.aux if steal is not None else wire_aux
            aux_specs = {k: P(geom.axr, geom.axc, *(None,) * (v.ndim - 2))
                         for k, v in aux.items()}
            with _obs.span("plan_build.commit"):
                self._aux = {
                    k: jax.device_put(
                        np.ascontiguousarray(v),
                        jax.sharding.NamedSharding(mesh, aux_specs[k]))
                    for k, v in aux.items()}
            if steal is not None:
                a_blocks, b_blocks = a_key[0] == "bsr", False

                def body(a, b, aux, geom):
                    return algorithm.body(a, b, aux, geom, steal)
            else:
                a_blocks, b_blocks = "a" in packs, "b" in packs
                body = algorithm.packed_body

            def fn(a, b, aux):
                return body(_local_view(a), _local_view(b),
                            {k: v[0, 0] for k, v in aux.items()}, geom)

            in_specs = (blocks_spec if a_blocks else specs[0],
                        blocks_spec if b_blocks else specs[1], aux_specs)
            out_specs = P(geom.axr, geom.axc)
        elif symbolic is None:
            body = algorithm.body

            def fn(a, b):
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
            with _obs.span("plan_build.commit"):
                sched = symbolic.scheduled_pairs(
                    algorithm.k_order,
                    pair_a=None if wire_aux is None else wire_aux.get("pa"),
                    pair_b=None if wire_aux is None else wire_aux.get("pb"))
                # Pair lists are plan-lifetime constants; commit them in
                # their mesh sharding once so repeated calls don't
                # re-transfer them to every device (measurably dominates
                # small multiplies).
                pair_sharding = jax.sharding.NamedSharding(
                    mesh, P(geom.axr, geom.axc, None, None))
                self._pairs = {
                    k: jax.device_put(np.asarray(v, dtype=np.int32),
                                      pair_sharding)
                    for k, v in sched.items()}
                self._c_rows = jnp.asarray(symbolic.c_rows, dtype=jnp.int32)
                self._c_cols = jnp.asarray(symbolic.c_cols, dtype=jnp.int32)
                self._c_counts = jnp.asarray(symbolic.c_counts,
                                             dtype=jnp.int32)
            # What a product's kernel calls will do: every device walks g
            # pair lists of one uniform length (so the busiest device walks
            # as many entries as any), in grid steps of pair_group entries
            # each; real pairs are the products of two real blocks, over
            # all devices.
            reg = _obs.registry()
            reg.gauge("plan.real_pairs", algorithm=algorithm.name).set(
                symbolic.total_real_pairs())
            reg.gauge("plan.pair_steps", algorithm=algorithm.name).set(
                geom.g * symbolic.pair_capacity)
            reg.gauge("plan.pair_grid_steps", algorithm=algorithm.name).set(
                geom.g * pair_grid_steps(
                    symbolic.pair_capacity, symbolic.block_size,
                    jnp.promote_types(a_key[-1], b_key[-1])))

            def fn(a, b, pairs):
                c = sparse_body(_local_view(a), _local_view(b),
                                _local_view(pairs), geom)
                return c[None, None]      # restore the (1, 1) grid dims

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
            self.traces += 1          # runs at trace time only
            for hook in list(_TRACE_HOOKS):
                hook(self)
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
        if _obs.drift_enabled():
            return self._call_recording_drift(a, b)
        # Spans on, drift off: the span covers the dispatch only; nothing
        # blocks and no cost model runs, so a traced product runs as an
        # untraced one does.
        with self._multiply_span():
            return self._execute(a, b)

    def _multiply_span(self):
        return _obs.span(f"multiply.{self.algorithm.name}", kind=self.kind,
                         wire=self.wire, output=self.output,
                         overlap=self.overlap)

    def _call_recording_drift(self, a, b):
        """One product under ``obs.enable(drift=True)``: block on it and
        record its measured time beside the cost model's prediction."""
        t0 = time.perf_counter()
        sp = self._multiply_span()
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
        if self._entry_cap:
            # encode before the blocks are placed: the encoder's work
            # space then sits beside one tile of the device's, not two
            a_tree = a_h.entry_wire(pl_a, self._entry_cap, on_mesh)
            return ({**a_tree, **a_h.placed(pl_a, on_mesh)},
                    b_h.placed(pl_b, on_mesh))
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
        if g is None:
            raise ValueError(
                "a dense left operand needs g=<grid size> or a DistDense "
                "handle (DistDense.from_global)")
        a_h = DistDense.from_global(a, g)
    if g is not None and a_h.g != g:
        raise ValueError(f"left operand lives on a {a_h.g}x{a_h.g} grid, "
                         f"but g={g} was requested")

    if isinstance(b, DistMatrix):
        b_h = b
    elif isinstance(b, TiledBSR):
        b_h = DistBSR.from_tiled(b)
    else:
        b_h = DistDense.for_rhs(b, a_h, allow_pad=allow_pad)

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


def _resolve_wire(wire: str, output: str, a_h: DistMatrix,
                  b_h: DistMatrix) -> str:
    """Resolve the ``wire=`` request ("auto" | "padded" | "packed").

    ``"auto"`` keeps today's behaviour for dense-output plans (padded
    wire, so structurally different operands with equal abstract shapes
    keep sharing one cached plan) and resolves to ``"packed"`` for
    sparse-output plans, which are specialized to the operands' structure
    anyway — there packing is a strict win.  (A dense-output ``"auto"``
    plan may still ship A as entries: :func:`_entry_cap_for`.)
    """
    if wire not in ("auto", "padded", "packed"):
        raise ValueError(f"unknown wire {wire!r}; one of "
                         "('auto', 'padded', 'packed')")
    if wire == "packed" and not (isinstance(a_h, DistBSR)
                                 or isinstance(b_h, DistBSR)):
        raise ValueError(
            "wire='packed' needs at least one block-sparse (DistBSR) "
            "operand — dense operands have no packable structure; use "
            "wire='padded'")
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


def _entry_cap_for(alg: Algorithm, a_h: DistMatrix) -> int:
    """The entry capacity A's stream ships at under ``wire="auto"``, or 0
    where it ships blocks: the schedule is ``ring_c``, A is block-sparse on
    a grid of more than one tile, and shipping and decoding its fullest
    tile's nonzero entries (counted on the devices,
    :meth:`DistBSR.entry_counts`) beats the exposed shift of its blocks
    (:func:`repro.core.entries.entries_win`)."""
    if alg.body is not _schedules._body_ring_c \
            or not isinstance(a_h, DistBSR) or a_h.g < 2:
        return 0
    cap = _entries.entry_capacity(a_h.entry_counts().max())
    t = a_h.tiled
    win = _entries.entries_win(cap, t.store_capacity, t.block_size,
                               jnp.dtype(t.dtype).itemsize)
    return cap if win else 0


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
    wire = _resolve_wire(wire, output, a_h, b_h)
    overlap = _resolve_overlap(overlap)
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
    (e.g. ``ring_a`` with a dense B) degrades to its padded plan.  Under
    ``"auto"``, a dense-output ``ring_c`` plan on g > 1 ships A's tile as
    its nonzero entries (``plan.wire == "entries"``,
    :mod:`repro.core.entries`) when shipping and decoding A's fullest
    tile's entries beats the exposed shift of its blocks (below about
    0.6% of a float32 tile's positions); such a plan joins the cache
    keyed on the entry capacity and refuses a left operand with more
    entries in a tile.

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
    wire_request = wire
    wire = _resolve_wire(wire, output, a_h, b_h)
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
    entry_cap = _entry_cap_for(alg, a_h) \
        if wire_request == "auto" and sym is None else 0
    if entry_cap:
        wire = "entries"
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
    if entry_cap:
        # the executable's shapes hold the entry capacity, not a structure
        key += ("wire-entries", entry_cap)
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
                          wire_fps=wire_fps, overlap=overlap,
                          entry_cap=entry_cap)
    if sym is None:
        _set_dense_output_gauges(plan, a_h, wire_aux)
    plan.validate(validate, a_h, b_h)
    if cache:
        _PLAN_CACHE[key] = plan
    return plan


def _set_dense_output_gauges(plan: MatmulPlan, a_h: DistMatrix,
                             wire_aux: Optional[Dict[str, np.ndarray]]
                             ) -> None:
    """What one product of a dense-output plan does on its busiest device,
    set as gauges labelled ``algorithm=`` and ``wire=``:

    ``plan.spmm_block_steps``, the SpMM kernel's grid steps: g steps, each
    walking the A list once for every column panel of B (one kernel call
    a step per A stream, each on its own share of B's columns);
    ``plan.spmm_real_blocks``, the real A blocks among them: an A that
    rides (``"a"`` in the schedule's wire) brings each tile of the
    device's grid row once, an A that stays (``ring_a``) is the device's
    own tile at every step; 0 and 0 where no SpMM kernel runs (a dense A,
    or steal3d's pair kernel);

    ``plan.wire_bytes``, the bytes its collectives send out of one device:
    each stream's tile at the wire's capacity times the transfers the body
    makes of it, g - 1 for a ring shift or an all-gather, g hops for
    ``ring_a``'s partial C, 2 (g - 1) for ``summa_bcast``'s psum
    broadcasts (what a ring all-reduce sends); a steal3d plan's own count
    of its gathers, moves and reductions.  Under ``wire="entries"`` A's
    tile is its entries (a value and an int32 position each) with its rows
    and cols;

    ``plan.wire_entries`` (labelled ``stream="a"`` too), the entries A's
    stream ships out of one device a product: g - 1 shifts of the entry
    capacity under ``wire="entries"``, 0 where it ships blocks."""
    alg, geom, g = plan.algorithm, plan.geom, plan.geom.g
    steps = real = 0
    if alg.static_planner is None and isinstance(a_h, DistBSR):
        length = wire_aux["a_rows"].shape[-1] if "a" in plan._packs \
            else a_h.tiled.store_capacity
        n_a = max(alg.wire.count("a"), 1)
        share = geom.tn // n_a
        widths = [share] * (n_a - 1) + [geom.tn - share * (n_a - 1)]
        panels = sum(w // spmm_block_n(w) for w in widths if w)
        counts = np.asarray(a_h.counts, dtype=np.int64)
        walked = counts.sum(axis=1).max() if "a" in alg.wire \
            else g * counts.max()
        steps, real = g * length * panels, int(walked) * panels
    if plan.steal is not None:
        sent = plan.steal.cost["total_net_bytes"]
    else:
        tiles, _ = _dense_output_tiles(geom, plan._a_key, plan._b_key,
                                       plan._wire_caps or {})
        if plan._entry_cap:
            store = plan._a_key[4] + geom.a_nbr
            tiles["a"] = _entries.entry_bytes(
                plan._entry_cap, np.dtype(_key_dtype(plan._a_key)).itemsize) \
                + store * 2 * 4
        per = 2 * (g - 1) if alg.style == "bsp" \
            and not alg.wire_amortized else g - 1
        # a tile named twice rides both ways round the ring, which at
        # g = 2 is one permutation: the compiler sends it once
        streams = alg.wire if g > 2 else set(alg.wire)
        sent = sum(tiles[t] * (g if t == "c" else per) for t in streams)
    reg = _obs.registry()
    labels = dict(algorithm=alg.name, wire=plan.wire)
    reg.gauge("plan.spmm_block_steps", **labels).set(steps)
    reg.gauge("plan.spmm_real_blocks", **labels).set(real)
    reg.gauge("plan.wire_bytes", **labels).set(float(sent))
    reg.gauge("plan.wire_entries", stream="a", **labels).set(
        (g - 1) * plan._entry_cap)


def plan_matmul(a, b, **kw) -> MatmulPlan:
    if not _obs.enabled():
        return _plan_matmul_impl(a, b, **kw)
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
