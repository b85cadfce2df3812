"""Static 3D work-grid dispatch: assignment properties + planner + plans.

Covers the ISSUE-4 contract for ``schedule.assign_3d_lpt`` (every (i,k,j)
item assigned exactly once, locality constraint respected, makespan never
worse than owner-computes), the ``core.steal3d`` plan builder's invariants
(pair conservation, index bounds, move/reduce round consistency), the
``steal3d`` algorithm end-to-end on a g=1 mesh against ``ring_c`` across
the dispatch matrix (real grids run in ``selftest --check steal3d`` via
``tests/test_distributed.py``), the auto-select cost entry, and the
satellite regressions (``steal_simulation`` zero guard, empty-operand
capacity-0 fast path).
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import api
from repro.core.api import DistBSR, DistDense, matmul, plan_matmul
from repro.core.bsr import TiledBSR, random_sparse, rmat_matrix
from repro.core.grid import ProcessGrid, bucket_capacity
from repro.core.schedule import (assign_3d_lpt, steal_simulation,
                                 stage_imbalance)

G = 1  # the main pytest process owns a single CPU device


# ---------------------------------------------------------------------------
# assign_3d_lpt
# ---------------------------------------------------------------------------
def _pareto_flops(g, seed, j_dep=False):
    rng = np.random.default_rng(seed)
    cost_ik = rng.pareto(1.1, size=(g, g)) + 0.01     # heavy-tailed R-MAT-ish
    if j_dep:
        return np.broadcast_to(cost_ik[:, :, None], (g, g, g)) \
            * (rng.random((g, g, g)) + 0.5)
    return np.broadcast_to(cost_ik[:, :, None], (g, g, g)).copy()


@pytest.mark.parametrize("g,seed", [(2, 0), (4, 1), (4, 2), (8, 3)])
@pytest.mark.parametrize("locality", ["none", "random", "locality"])
def test_assign_3d_every_item_assigned_once(g, seed, locality):
    flops = _pareto_flops(g, seed, j_dep=True)
    asg = assign_3d_lpt(flops, g, locality=locality)
    assert asg.dev.shape == (g, g, g)
    assert asg.dev.min() >= 0 and asg.dev.max() < g * g
    # loads reconstruct exactly from the assignment + penalty convention
    penalty = {"none": 1.0, "random": 1.0 + asg.comm_penalty,
               "locality": 1.0 + asg.comm_penalty / 3.0}[locality]
    ii, _, jj = np.meshgrid(np.arange(g), np.arange(g), np.arange(g),
                            indexing="ij")
    owner = ii * g + jj
    eff = np.where(asg.dev == owner, flops, flops * penalty)
    loads = np.zeros(g * g)
    np.add.at(loads, asg.dev.ravel(), eff.ravel())
    np.testing.assert_allclose(loads, asg.loads)
    assert asg.makespan == pytest.approx(loads.max())


@pytest.mark.parametrize("g,seed", [(2, 0), (4, 1), (4, 5), (8, 2)])
def test_assign_3d_locality_constraint(g, seed):
    """Under locality, item (i, k, j) only lands in grid row i or col j."""
    asg = assign_3d_lpt(_pareto_flops(g, seed), g, locality="locality")
    r, c = asg.dev // g, asg.dev % g
    i = np.arange(g)[:, None, None]
    j = np.arange(g)[None, None, :]
    assert bool(((r == i) | (c == j)).all())


@pytest.mark.parametrize("g,seed", [(2, 0), (4, 1), (4, 7), (8, 2), (8, 9)])
@pytest.mark.parametrize("locality", ["random", "locality"])
def test_assign_3d_makespan_never_worse_than_owner(g, seed, locality):
    asg = assign_3d_lpt(_pareto_flops(g, seed, j_dep=True), g,
                        locality=locality)
    assert asg.makespan <= asg.owner_makespan + 1e-9
    assert asg.gain() >= 1.0


def test_assign_3d_skew_beats_owner_computes():
    """One hub grid row owning most of the work: stealing must help."""
    g = 4
    flops = np.ones((g, g, g))
    flops[0] = 50.0                       # grid row 0 is the hub
    asg = assign_3d_lpt(flops, g, locality="locality")
    assert asg.n_moved > 0
    assert asg.makespan < asg.owner_makespan
    # the simulation's equilibrium agrees that stealing wins here
    sim = steal_simulation(flops[:, :, 0], steal="locality")
    none = steal_simulation(flops[:, :, 0], steal="none")
    assert sim < none


def test_assign_3d_owner_mode_and_zero_items():
    g = 3
    flops = np.zeros((g, g, g))
    flops[1, 1, 1] = 5.0
    owner = assign_3d_lpt(flops, g, locality="none")
    assert owner.n_moved == 0
    loc = assign_3d_lpt(flops, g, locality="locality")
    # zero-cost items never move; the single real item stays feasible
    assert (loc.dev[flops == 0] ==
            owner.dev[flops == 0]).all()


def test_assign_3d_max_stolen_caps_offowner_items():
    g = 4
    flops = np.ones((g, g, g))
    flops[0] = 100.0
    asg = assign_3d_lpt(flops, g, locality="locality", max_stolen=1)
    ii, _, jj = np.meshgrid(np.arange(g), np.arange(g), np.arange(g),
                            indexing="ij")
    owner = ii * g + jj
    stolen_per_dev = np.zeros(g * g, dtype=int)
    np.add.at(stolen_per_dev, asg.dev[asg.dev != owner].ravel(), 1)
    assert stolen_per_dev.max() <= 1


def test_assign_3d_validates_inputs():
    with pytest.raises(ValueError, match="flops_ikj"):
        assign_3d_lpt(np.ones((2, 3, 2)), 2)
    with pytest.raises(ValueError, match="locality"):
        assign_3d_lpt(np.ones((2, 2, 2)), 2, locality="quantum")


# ---------------------------------------------------------------------------
# Satellite regressions: steal_simulation zero guard, empty fast path
# ---------------------------------------------------------------------------
def test_steal_simulation_all_empty_returns_one_not_nan():
    """All-empty tile_costs (legal for hypersparse operands) used to
    divide by loads.mean() == 0 and return NaN."""
    z = np.zeros((4, 4))
    for steal in ("none", "random", "locality"):
        v = steal_simulation(z, steal=steal)
        assert v == 1.0 and not np.isnan(v)
    assert stage_imbalance(z) == (1.0, 1.0)   # the guard steal_sim now copies


def test_bucket_capacity_zero_is_zero():
    assert bucket_capacity(0) == 0
    assert bucket_capacity(1) == 1


def test_empty_operand_capacity_zero_through_plan():
    """A genuinely empty DistBSR allocates no phantom block storage and
    multiplies to zeros end-to-end through plan_matmul (satellite)."""
    empty = DistBSR.from_dense(np.zeros((32, 32), np.float32), g=G,
                               block_size=4)
    assert empty.capacity == 0
    # store_capacity is the coverage blocks only: the cheap empty path
    assert empty.tiled.store_capacity == empty.tiled.tile_shape[0] // 4
    b_h = DistDense.for_rhs(jnp.ones((32, 8), jnp.float32), empty)
    for alg in api.algorithms():
        got = np.asarray(matmul(empty, b_h, algorithm=alg, impl="ref"))
        np.testing.assert_array_equal(got, np.zeros((32, 8), np.float32))
    # sparse output of an empty product also keeps capacity 0
    c = matmul(empty, empty, algorithm="ring_c", impl="ref",
               output="sparse")
    assert c.capacity == 0
    np.testing.assert_array_equal(np.asarray(c.densify()),
                                  np.zeros((32, 32), np.float32))


# ---------------------------------------------------------------------------
# Plan builder invariants (host-side, real 4x4 geometry, no mesh needed)
# ---------------------------------------------------------------------------
def _skewed_handle(g=4, scale=8, bs=8):
    return DistBSR.from_dense(rmat_matrix(scale=scale, edgefactor=8, seed=3),
                              g=g, block_size=bs)


def _steal_plan_4x4():
    a_h = _skewed_handle()
    b_h = DistDense.for_rhs(jnp.ones((a_h.shape[1], 32), jnp.float32), a_h)
    geom = api._geometry(a_h, b_h, impl=None, axis_row="row",
                         axis_col="col")
    return a_h, api._steal_plan_for(a_h, b_h, geom)


def test_steal_plan_pair_conservation_and_bounds():
    """Every real A block of every (i, k) tile appears exactly g times
    across the fleet's pair lists (once per output column j), plus one
    coverage pair per output slot per device."""
    a_h, sp = _steal_plan_4x4()
    g = sp.g
    counts = np.asarray(a_h.counts)
    total_real = int(counts.sum()) * g
    pa, ps = sp.aux["pa"], sp.aux["ps"]
    # zero/coverage pairs all reference the appended zero tile's slots
    zero_base = (g + sum(sp.a_move_cap)) * sp.store_a
    real_mask = pa < zero_base
    assert int(real_mask.sum()) == total_real
    assert pa.max() < zero_base + sp.store_a
    assert ps.min() >= 0 and ps.max() < sp.n_slots
    assert sp.aux["pb"].min() >= 0
    assert sp.aux["pb"].max() < (g + sum(sp.b_move_cap)) * sp.b_chunks
    # slot lists are nondecreasing per device (the kernel contract) and
    # every slot is covered on every device
    for r in range(g):
        for c in range(g):
            s = ps[r, c]
            assert (np.diff(s) >= 0).all()
            assert len(np.unique(s)) == sp.n_slots
    # the pair capacity is the (bucketed) realized makespan: it must beat
    # the owner-computes rings' uniform g x store padding on skewed input
    ring_work = g * a_h.tiled.store_capacity
    assert sp.pair_capacity < ring_work


def test_steal_plan_makespan_and_cost_fields():
    _, sp = _steal_plan_4x4()
    asg = sp.assignment
    assert asg.makespan <= asg.owner_makespan
    cm = sp.cost
    for key in ("total_flops", "total_net_bytes", "ai_net", "ai_local",
                "n_msgs", "gather_bytes", "moved_tile_bytes",
                "reduce_bytes", "lpt_makespan", "owner_makespan"):
        assert key in cm
    assert cm["total_net_bytes"] == pytest.approx(
        cm["gather_bytes"] + cm["moved_tile_bytes"] + cm["reduce_bytes"])
    assert cm["n_msgs"] >= 2.0


def test_steal_plan_memoized_on_structure():
    a_h = _skewed_handle()
    b_h = DistDense.for_rhs(jnp.ones((a_h.shape[1], 32), jnp.float32), a_h)
    geom = api._geometry(a_h, b_h, impl=None, axis_row="row",
                         axis_col="col")
    api.clear_plan_cache()
    sp1 = api._steal_plan_for(a_h, b_h, geom)
    sp2 = api._steal_plan_for(a_h, b_h, geom)
    assert sp1 is sp2


# ---------------------------------------------------------------------------
# Dispatch matrix (g=1; real grids in selftest --check steal3d)
# ---------------------------------------------------------------------------
@pytest.fixture
def operands():
    a_d = random_sparse(16, 16, 0.3, seed=0)
    b = np.random.default_rng(0).standard_normal((16, 8)).astype(np.float32)
    b_sp = random_sparse(16, 16, 0.25, seed=1)
    a_h = DistBSR.from_dense(a_d, g=G, block_size=4)
    b_h = DistDense.for_rhs(jnp.asarray(b), a_h)
    b_sph = DistBSR.from_dense(b_sp, g=G, block_size=4)
    return a_d, b, b_sp, a_h, b_h, b_sph


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_steal3d_allclose_ring_c_spmm(operands, impl):
    a_d, b, _, a_h, b_h, _ = operands
    got = np.asarray(matmul(a_h, b_h, algorithm="steal3d", impl=impl))
    ring = np.asarray(matmul(a_h, b_h, algorithm="ring_c", impl=impl))
    np.testing.assert_allclose(got, ring, atol=1e-5)
    np.testing.assert_allclose(got, a_d @ b, atol=1e-5)


@pytest.mark.parametrize("overlap", ["auto", "on"])
def test_steal3d_chunked_pair_lists_match_ref(operands, monkeypatch,
                                              overlap):
    """Steal pair lists past the SMEM budget (here shrunk to 16 entries
    per list) are laid out in chunks at output-row boundaries and run as
    several kernel calls into one output."""
    import jax
    from repro.kernels import bsr_spmm
    a_d, b, _, a_h, b_h, _ = operands
    monkeypatch.setattr(bsr_spmm, "SMEM_LIST_BYTES", 2 * 4 * 16)
    api.clear_plan_cache()
    jax.clear_caches()
    try:
        plan = api.plan_matmul(a_h, b_h, algorithm="steal3d",
                               impl="interpret", overlap=overlap)
        pa = plan.steal.aux["pa" if overlap == "auto" else "pa0"]
        assert pa.shape[-1] > 16 and pa.shape[-1] % 16 == 0
        got = np.asarray(plan(a_h, b_h))
    finally:
        api.clear_plan_cache()
        jax.clear_caches()
    np.testing.assert_allclose(got, a_d @ b, atol=1e-5)


def test_steal3d_allclose_ring_c_spgemm(operands):
    a_d, _, b_sp, a_h, _, b_sph = operands
    got = np.asarray(matmul(a_h, b_sph, algorithm="steal3d", impl="ref"))
    ring = np.asarray(matmul(a_h, b_sph, algorithm="ring_c", impl="ref"))
    np.testing.assert_allclose(got, ring, atol=1e-5)
    np.testing.assert_allclose(got, a_d @ b_sp, atol=1e-5)


def test_steal3d_allclose_ring_c_dense():
    a = np.random.default_rng(1).standard_normal((10, 7)).astype(np.float32)
    b = np.random.default_rng(2).standard_normal((7, 5)).astype(np.float32)
    got = np.asarray(matmul(jnp.asarray(a), jnp.asarray(b), g=G,
                            algorithm="steal3d"))
    ring = np.asarray(matmul(jnp.asarray(a), jnp.asarray(b), g=G,
                             algorithm="ring_c"))
    assert got.shape == (10, 5)
    np.testing.assert_allclose(got, ring, atol=1e-5)


def test_steal3d_plan_traces_once_and_rejects_structure_mismatch(operands):
    _, _, _, a_h, b_h, _ = operands
    api.clear_plan_cache()
    plan = plan_matmul(a_h, b_h, algorithm="steal3d", impl="ref")
    for _ in range(3):
        plan(a_h, b_h)
    assert plan.traces == 1
    # same abstract shapes, different structure: the cached plan must not
    # serve it, and calling it directly must fail fast
    other = DistBSR.from_dense(
        random_sparse(16, 16, 0.02, seed=9), g=G, block_size=4,
        capacity=a_h.capacity)
    assert other.abstract_key() == a_h.abstract_key()
    assert other.structure_key() != a_h.structure_key()
    plan2 = plan_matmul(other, b_h, algorithm="steal3d", impl="ref")
    assert plan2 is not plan
    with pytest.raises(ValueError, match="structure"):
        plan(other, b_h)


def test_steal3d_sparse_output_refused(operands):
    _, _, _, a_h, _, b_sph = operands
    with pytest.raises(ValueError, match="sparse-output"):
        plan_matmul(a_h, b_sph, algorithm="steal3d", output="sparse")


# ---------------------------------------------------------------------------
# auto_select integration: the equilibrium score
# ---------------------------------------------------------------------------
def test_auto_scores_include_steal3d(operands):
    _, _, _, a_h, b_h, _ = operands
    choice, scores = api.auto_select(a_h, b_h)
    assert "steal3d" in scores
    assert scores["steal3d"] > 0


def test_auto_picks_steal3d_when_stealing_wins_on_skew():
    """Skewed R-MAT on a 4x4 grid where the simulation says stealing wins:
    in the compute-bound regime (the CI harness machine) the steal3d cost
    entry — scored with the realized equilibrium makespan — beats every
    owner-computes schedule, so auto selects it.  Scoring is mesh-free, so
    the real 4x4 geometry runs in-process."""
    from repro.core.roofline import HOST_CPU, TPU_V5E
    a_h = _skewed_handle(scale=11, bs=16)
    b_h = DistDense.for_rhs(
        jnp.ones((a_h.shape[1], 256), jnp.float32), a_h)
    counts = np.asarray(a_h.counts, dtype=np.float64)
    sim_steal = steal_simulation(counts, steal="locality")
    sim_none = steal_simulation(counts, steal="none")
    assert sim_steal < sim_none           # stealing wins in the simulation
    choice, scores = api.auto_select(a_h, b_h, machine=HOST_CPU)
    assert choice == "steal3d"
    assert scores["steal3d"] == min(scores.values())
    # on the net-bound nominal v5e constants, shipping extra tiles to
    # steal work must NOT look free — auto keeps an owner-computes ring
    v5e_choice, v5e_scores = api.auto_select(a_h, b_h, machine=TPU_V5E)
    assert v5e_choice != "steal3d"


def test_steal3d_cost_scales_with_makespan_not_capacity():
    """The steal3d flop term tracks the LPT makespan: a skewed matrix's
    steal3d cost model must charge fewer executed flops than ring_c's
    uniform g x store padding.  (Cost models are mesh-free.)"""
    a_h = _skewed_handle()
    b_h = DistDense.for_rhs(jnp.ones((a_h.shape[1], 32), jnp.float32), a_h)
    geom = api._geometry(a_h, b_h, impl=None, axis_row="row",
                         axis_col="col")
    sp = api._steal_plan_for(a_h, b_h, geom)
    ring_cm = api._cost_model(api.REGISTRY.get("ring_c"), geom,
                              a_h.abstract_key(), b_h.abstract_key())
    assert sp.cost["total_flops"] < ring_cm["total_flops"]
