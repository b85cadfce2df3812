"""Plan-based distributed-matmul API tests (single-device, g=1 grid).

Multi-device behaviour (2x2/3x3 grids) is covered by the subprocess
selftests in ``tests/test_distributed.py``; here we verify the API
contract in-process: registry dispatch for every algorithm x operand-kind
combination against dense references, plan reuse (one trace for repeated
calls, vs. a retrace per call on the legacy uncached path), placement-state
caching on DistMatrix handles, bit-identical deprecation shims, mesh and
inner-dimension validation, the cost model, and the examples/benchmarks
API-hygiene guard.
"""
import importlib.util
import pathlib
import warnings

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import api
from repro.core.api import (REGISTRY, Algorithm, DistBSR, DistDense, matmul,
                            plan_matmul)
from repro.core.bsr import TiledBSR, random_sparse
from repro.core.dist import make_grid_mesh
from repro.core.grid import ProcessGrid

G = 1  # the main pytest process owns a single CPU device


@pytest.fixture
def operands():
    a_d = random_sparse(16, 16, 0.3, seed=0)
    b = np.random.default_rng(0).standard_normal((16, 8)).astype(np.float32)
    b_sp = random_sparse(16, 16, 0.25, seed=1)
    a_h = DistBSR.from_dense(a_d, g=G, block_size=4)
    b_h = DistDense.for_rhs(jnp.asarray(b), a_h)
    b_sph = DistBSR.from_dense(b_sp, g=G, block_size=4)
    return a_d, b, b_sp, a_h, b_h, b_sph


# ---------------------------------------------------------------------------
# Dispatch: every registered algorithm x {spmm, spgemm, dense}
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("alg", api.algorithms())
def test_dispatch_spmm(operands, alg):
    a_d, b, _, a_h, b_h, _ = operands
    plan = plan_matmul(a_h, b_h, algorithm=alg, impl="ref")
    assert plan.kind == "spmm"
    got = np.asarray(matmul(a_h, b_h, algorithm=alg, impl="ref"))
    np.testing.assert_allclose(got, a_d @ b, atol=1e-5)


@pytest.mark.parametrize("alg", api.algorithms())
def test_dispatch_spgemm(operands, alg):
    a_d, _, b_sp, a_h, _, b_sph = operands
    plan = plan_matmul(a_h, b_sph, algorithm=alg, impl="ref")
    assert plan.kind == "spgemm"
    got = np.asarray(matmul(a_h, b_sph, algorithm=alg, impl="ref"))
    np.testing.assert_allclose(got, a_d @ b_sp, atol=1e-5)


@pytest.mark.parametrize("alg", api.algorithms())
def test_dispatch_dense(alg):
    a = np.random.default_rng(1).standard_normal((10, 7)).astype(np.float32)
    b = np.random.default_rng(2).standard_normal((7, 5)).astype(np.float32)
    plan = plan_matmul(jnp.asarray(a), jnp.asarray(b), g=G, algorithm=alg)
    assert plan.kind == "dense"
    got = np.asarray(matmul(jnp.asarray(a), jnp.asarray(b), g=G,
                            algorithm=alg))
    # logical-shape crop applies uniformly (the dense path used to skip it)
    assert got.shape == (10, 5)
    np.testing.assert_allclose(got, a @ b, atol=1e-5)


def test_dense_sparse_not_implemented(operands):
    *_, b_sph = operands
    a = jnp.ones((16, 16), jnp.float32)
    with pytest.raises(NotImplementedError):
        matmul(DistDense.from_global(a, G), b_sph)


# ---------------------------------------------------------------------------
# Plan reuse: trace counts
# ---------------------------------------------------------------------------
def test_plan_reuse_traces_once(operands):
    """10 calls of one plan: the executable is traced exactly once."""
    _, _, _, a_h, b_h, _ = operands
    api.clear_plan_cache()
    seen = []
    hook = api.add_trace_hook(lambda plan: seen.append(plan))
    try:
        plan = plan_matmul(a_h, b_h, algorithm="ring_c", impl="ref")
        outs = [np.asarray(plan(a_h, b_h)) for _ in range(10)]
    finally:
        api.remove_trace_hook(hook)
    assert plan.traces == 1
    assert len(seen) == 1 and seen[0] is plan
    for o in outs[1:]:
        np.testing.assert_array_equal(o, outs[0])


def test_legacy_fresh_plans_retrace_every_call(operands):
    """cache=False reproduces the legacy per-call behaviour: N traces."""
    _, _, _, a_h, b_h, _ = operands
    n_calls = 4
    seen = []
    hook = api.add_trace_hook(lambda plan: seen.append(plan))
    try:
        for _ in range(n_calls):
            fresh = plan_matmul(a_h, b_h, algorithm="ring_c", impl="ref",
                                cache=False)
            fresh(a_h, b_h)
    finally:
        api.remove_trace_hook(hook)
    assert len(seen) == n_calls


def test_shims_share_plan_cache_and_match_bitwise(operands):
    a_d, b, _, a_h, b_h, _ = operands
    from repro.core import spmm as legacy
    api.clear_plan_cache()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        old1 = np.asarray(legacy.spmm(a_h.tiled, jnp.asarray(b),
                                      algorithm="ring_c", impl="ref"))
        old2 = np.asarray(legacy.spmm(a_h.tiled, jnp.asarray(b),
                                      algorithm="ring_c", impl="ref"))
    assert api.plan_cache_size() == 1     # both calls hit one shared plan
    new = np.asarray(matmul(a_h, b_h, algorithm="ring_c", impl="ref"))
    np.testing.assert_array_equal(old1, old2)
    np.testing.assert_array_equal(old1, new)   # bit-identical, same engine


def test_shim_spgemm_and_dense_match_bitwise(operands):
    a_d, _, b_sp, a_h, _, b_sph = operands
    from repro.core import spmm as legacy
    da = np.random.default_rng(5).standard_normal((12, 9)).astype(np.float32)
    db = np.random.default_rng(6).standard_normal((9, 6)).astype(np.float32)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        old_sp = np.asarray(legacy.spgemm(a_h.tiled, b_sph.tiled,
                                          algorithm="ring_a", impl="ref"))
        old_d = np.asarray(legacy.dense_matmul(da, db, g=G,
                                               algorithm="ring_a"))
    new_sp = np.asarray(matmul(a_h, b_sph, algorithm="ring_a", impl="ref"))
    new_d = np.asarray(matmul(jnp.asarray(da), jnp.asarray(db), g=G,
                              algorithm="ring_a"))
    np.testing.assert_array_equal(old_sp, new_sp)
    np.testing.assert_array_equal(old_d, new_d)


def test_shims_warn_deprecation(operands):
    _, b, _, a_h, _, _ = operands
    from repro.core import spmm as legacy
    with pytest.warns(DeprecationWarning):
        legacy.spmm(a_h.tiled, jnp.asarray(b), impl="ref")


# ---------------------------------------------------------------------------
# Placement-state caching on handles
# ---------------------------------------------------------------------------
def test_placement_materialized_once(operands):
    _, _, _, a_h, b_h, _ = operands
    t1 = a_h.placed("skew_rows")
    t2 = a_h.placed("skew_rows")
    assert t1 is t2                      # skew applied at most once
    d1 = b_h.placed("skew_cols")
    assert d1 is b_h.placed("skew_cols")
    # on the 1x1 grid every placement is the natural tree: no copy at all
    assert t1 is a_h.placed("natural") and d1 is b_h.placed("natural")
    assert set(a_h.placements()) == {"natural"}


def test_placement_reused_across_plans(operands):
    _, _, _, a_h, b_h, _ = operands
    matmul(a_h, b_h, algorithm="ring_c", impl="ref")
    placed_before = a_h.placed("skew_rows")
    matmul(a_h, b_h, algorithm="ring_c", impl="ref")   # second multiply
    assert a_h.placed("skew_rows") is placed_before


def test_unknown_placement_rejected(operands):
    _, _, _, a_h, _, _ = operands
    with pytest.raises(ValueError, match="placement"):
        a_h.placed("diagonal")


# ---------------------------------------------------------------------------
# Validation: mesh and inner dimensions
# ---------------------------------------------------------------------------
def test_mesh_wrong_axis_names_rejected(operands):
    _, _, _, a_h, b_h, _ = operands
    bad = make_grid_mesh(1, "r", "c")
    with pytest.raises(ValueError, match="axes"):
        plan_matmul(a_h, b_h, mesh=bad)


def test_mesh_wrong_shape_rejected():
    # operands on a 2x2 grid, mesh is 1x1: caught before any shard_map
    a_t = TiledBSR.from_dense(random_sparse(16, 16, 0.3, seed=2),
                              ProcessGrid(2, 2), block_size=4)
    b = jnp.ones((16, 4), jnp.float32)
    with pytest.raises(ValueError, match="process grid"):
        plan_matmul(a_t, b, mesh=make_grid_mesh(1))


def test_inner_dim_mismatch_needs_allow_pad(operands):
    a_d, _, _, a_h, _, _ = operands
    b_short = np.random.default_rng(7).standard_normal(
        (12, 8)).astype(np.float32)
    with pytest.raises(ValueError, match="allow_pad"):
        matmul(a_h, jnp.asarray(b_short))
    got = np.asarray(matmul(a_h, jnp.asarray(b_short), allow_pad=True,
                            impl="ref"))
    np.testing.assert_allclose(got, a_d[:, :12] @ b_short, atol=1e-5)


def test_inner_dim_overflow_always_rejected(operands):
    _, _, _, a_h, _, _ = operands
    b_long = jnp.ones((20, 8), jnp.float32)
    with pytest.raises(ValueError, match="inner dimensions disagree"):
        matmul(a_h, b_long, allow_pad=True)


def test_plan_rejects_mismatched_operands(operands):
    _, _, _, a_h, b_h, _ = operands
    plan = plan_matmul(a_h, b_h, algorithm="ring_c", impl="ref")
    other = DistDense.from_global(jnp.ones((16, 12), jnp.float32), G)
    with pytest.raises(ValueError, match="plan"):
        plan(a_h, other)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------
def test_registry_unknown_algorithm(operands):
    _, _, _, a_h, b_h, _ = operands
    with pytest.raises(ValueError, match="unknown algorithm"):
        matmul(a_h, b_h, algorithm="cannon")


def test_registry_rejects_duplicates():
    alg = REGISTRY.get("ring_c")
    with pytest.raises(ValueError, match="already registered"):
        REGISTRY.register(Algorithm(name="ring_c", body=alg.body))


def test_registry_extension_dispatches(operands):
    """A newly registered algorithm is immediately reachable via matmul."""
    a_d, b, _, a_h, b_h, _ = operands
    ring_c = REGISTRY.get("ring_c")
    REGISTRY.register(Algorithm(
        name="ring_c_clone", body=ring_c.body,
        a_placement=ring_c.a_placement, b_placement=ring_c.b_placement,
        unskew_out=ring_c.unskew_out, wire=ring_c.wire))
    try:
        got = np.asarray(matmul(a_h, b_h, algorithm="ring_c_clone",
                                impl="ref"))
        np.testing.assert_allclose(got, a_d @ b, atol=1e-5)
    finally:
        REGISTRY.unregister("ring_c_clone")
    assert "ring_c_clone" not in REGISTRY


def test_plan_cache_keys_on_allow_pad(operands):
    """allow_pad=True and =False must not share a cached plan."""
    _, _, _, a_h, b_h, _ = operands
    api.clear_plan_cache()
    strict = plan_matmul(a_h, b_h, algorithm="ring_c", impl="ref")
    padding = plan_matmul(a_h, b_h, algorithm="ring_c", impl="ref",
                          allow_pad=True)
    assert strict is not padding
    b_short = np.random.default_rng(8).standard_normal(
        (12, 8)).astype(np.float32)
    padding(a_h, jnp.asarray(b_short))           # pads: ok
    with pytest.raises(ValueError, match="allow_pad"):
        strict(a_h, jnp.asarray(b_short))        # strict plan still strict


def test_reregistering_algorithm_evicts_stale_plans(operands):
    a_d, b, _, a_h, b_h, _ = operands
    ring_c = REGISTRY.get("ring_c")
    bcast = REGISTRY.get("summa_bcast")
    name = "evict_probe"
    REGISTRY.register(Algorithm(
        name=name, body=ring_c.body, a_placement=ring_c.a_placement,
        b_placement=ring_c.b_placement, unskew_out=ring_c.unskew_out,
        wire=ring_c.wire))
    try:
        p1 = plan_matmul(a_h, b_h, algorithm=name, impl="ref")
        REGISTRY.register(Algorithm(name=name, body=bcast.body),
                          overwrite=True)
        p2 = plan_matmul(a_h, b_h, algorithm=name, impl="ref")
        assert p2 is not p1                      # stale plan evicted
        assert p2.algorithm.a_placement == "natural"
        np.testing.assert_allclose(
            np.asarray(p2(a_h, b_h)), a_d @ b, atol=1e-5)
    finally:
        REGISTRY.unregister(name)


def test_legacy_algorithms_tuple_matches_registry():
    from repro.core import spmm as legacy
    assert legacy.ALGORITHMS == api.algorithms()
    assert set(legacy.ALGORITHMS) == {"summa_bcast", "summa_ag", "ring_c",
                                      "ring_a", "ring_c_bidir", "steal3d"}


# ---------------------------------------------------------------------------
# Cost model
# ---------------------------------------------------------------------------
def test_cost_model_and_roofline(operands):
    _, _, _, a_h, b_h, _ = operands
    plan = plan_matmul(a_h, b_h, algorithm="ring_c", impl="ref")
    cm = plan.cost_model(a_h)
    assert cm["flops_per_step"] > 0 and cm["net_bytes_per_step"] > 0
    assert cm["ai_net"] == pytest.approx(
        cm["total_flops"] / cm["total_net_bytes"])
    assert cm["per_stage_imbalance"] >= cm["end_to_end_imbalance"] >= 1.0
    from repro.core.roofline import TPU_V5E
    perf = plan.predicted_perf(TPU_V5E)
    assert 0 < perf["perf"] <= TPU_V5E.arith_peak


def test_cost_model_ring_a_ships_c_not_a(operands):
    _, _, _, a_h, b_h, _ = operands
    ring_a = plan_matmul(a_h, b_h, algorithm="ring_a", impl="ref")
    ring_c = plan_matmul(a_h, b_h, algorithm="ring_c", impl="ref")
    assert ring_a.algorithm.wire == ("b", "c")
    assert ring_c.algorithm.wire == ("a", "b")
    assert ring_a.cost_model()["net_bytes_per_step"] != \
        ring_c.cost_model()["net_bytes_per_step"]


# ---------------------------------------------------------------------------
# Balanced tiling (balance="rows"): capacity shrink + epilogue inversion
# ---------------------------------------------------------------------------
def _skewed_rmat(scale=8):
    from repro.core.bsr import rmat_matrix
    return rmat_matrix(scale=scale, edgefactor=8, seed=3)  # unpermuted: skewed


def _manual_balanced_handle(d, block_size, seed=0):
    """A DistBSR carrying an explicit row-block permutation.

    On a 1x1 grid the balancer correctly falls back to the identity (one
    tile — no capacity to shrink), so epilogue-inversion tests manufacture
    the permuted value the way balance="rows" would on a real grid.
    """
    import dataclasses
    nbr = d.shape[0] // block_size
    perm = np.random.default_rng(seed).permutation(nbr)
    dp = d.reshape(nbr, block_size, -1)[perm].reshape(d.shape)
    t = TiledBSR.from_dense(dp, ProcessGrid(1, 1), block_size)
    t = dataclasses.replace(t, row_block_perm=tuple(int(p) for p in perm))
    return DistBSR.from_tiled(t)


def test_balance_rows_shrinks_capacity_and_waste():
    """R-MAT row-block balancing reduces uniform capacity on a 4x4 grid.

    Pure construction — no mesh needed, so the real multi-device geometry
    can be checked in-process.
    """
    d = _skewed_rmat()
    none = TiledBSR.from_dense(d, ProcessGrid(4, 4), block_size=8)
    rows = TiledBSR.from_dense(d, ProcessGrid(4, 4), block_size=8,
                               balance="rows")
    assert rows.capacity < none.capacity
    assert rows.padded_flop_waste() < none.padded_flop_waste()
    assert none.row_block_perm is None
    assert sorted(rows.row_block_perm) == list(range(d.shape[0] // 8))
    # the balanced matrix is a pure row-block permutation of the original
    inv = np.argsort(np.asarray(rows.row_block_perm))
    back = np.asarray(rows.to_dense()).reshape(-1, 8, d.shape[1])[inv]
    np.testing.assert_array_equal(back.reshape(d.shape), d)


@pytest.mark.parametrize("alg", ["ring_c", "ring_a", "ring_c_bidir"])
def test_balanced_plan_matches_unbalanced(alg):
    """Epilogue inverts the carried row permutation: results are allclose.

    (Real-grid balance="rows" plans are checked the same way by selftest
    --check balance on 2x2/3x3 meshes.)"""
    d = _skewed_rmat()
    b = np.random.default_rng(2).standard_normal((256, 16)).astype(np.float32)
    h_none = DistBSR.from_dense(d, g=G, block_size=8)
    h_rows = _manual_balanced_handle(d, 8)
    assert list(h_rows.row_block_perm) != sorted(h_rows.row_block_perm)
    c_none = np.asarray(matmul(h_none, jnp.asarray(b), algorithm=alg,
                               impl="ref"))
    c_rows = np.asarray(matmul(h_rows, jnp.asarray(b), algorithm=alg,
                               impl="ref"))
    np.testing.assert_allclose(c_rows, c_none, atol=1e-4)
    np.testing.assert_allclose(c_rows, d @ b, atol=1e-3)


def test_balance_identity_fallback_on_1x1_grid():
    """One tile -> no capacity to shrink: the balancer must return the
    identity layout (no carried perm) instead of a useless permutation."""
    h = DistBSR.from_dense(_skewed_rmat(), g=1, block_size=8,
                           balance="rows")
    assert h.row_block_perm is None


def test_balanced_spgemm_left_operand(operands):
    a_d, _, b_sp, _, _, b_sph = operands
    a_bal = _manual_balanced_handle(a_d, 4)
    got = np.asarray(matmul(a_bal, b_sph, algorithm="ring_c", impl="ref"))
    np.testing.assert_allclose(got, a_d @ b_sp, atol=1e-5)


def test_balanced_right_operand_rejected(operands):
    _, _, b_sp, a_h, _, _ = operands
    b_bal = _manual_balanced_handle(b_sp, 4)
    with pytest.raises(ValueError, match="right operand"):
        matmul(a_h, b_bal, impl="ref")


def test_from_tiled_balance_keeps_explicit_capacity():
    """Rebuilding with balance must not silently re-derive a capacity the
    caller pinned (abstract keys would stop matching cached plans)."""
    d = _skewed_rmat()
    pinned = TiledBSR.from_dense(d, ProcessGrid(4, 4), block_size=8,
                                 capacity=64)
    h = DistBSR.from_tiled(pinned, balance="rows")
    assert h.capacity == 64


def test_from_tiled_capacity_rejected_when_not_rebuilding():
    """capacity= is only honored on the re-tiling path; silently ignoring
    it would desync abstract keys from sibling pinned handles."""
    t = TiledBSR.from_dense(_skewed_rmat(), ProcessGrid(4, 4), block_size=8)
    with pytest.raises(ValueError, match="capacity"):
        DistBSR.from_tiled(t, capacity=256)


def test_from_tiled_balance_roundtrip():
    d = _skewed_rmat()
    plain = TiledBSR.from_dense(d, ProcessGrid(4, 4), block_size=8)
    h = DistBSR.from_tiled(plain, balance="rows", capacity=None)
    assert h.row_block_perm is not None        # skewed R-MAT: perm kept
    assert h.capacity < plain.capacity         # capacity=None: re-derived
    np.testing.assert_array_equal(
        np.asarray(h.tiled.to_dense()).reshape(-1, 8, 256)[
            np.argsort(np.asarray(h.row_block_perm))].reshape(256, 256), d)
    with pytest.raises(ValueError, match="balance"):
        DistBSR.from_tiled(plain, balance="columns")


# ---------------------------------------------------------------------------
# Auto-scheduling: algorithm="auto" picks the min-cost schedule
# ---------------------------------------------------------------------------
def test_auto_plan_picks_min_score_and_is_correct(operands):
    a_d, b, _, a_h, b_h, _ = operands
    # plan.requested reflects the request that FIRST built the plan; start
    # from an empty cache so earlier tests' explicit-name plans can't alias
    api.clear_plan_cache()
    plan = plan_matmul(a_h, b_h, algorithm="auto", impl="ref")
    assert plan.requested == "auto"
    assert set(plan.auto_scores) == set(api.algorithms())
    best = min(plan.auto_scores, key=plan.auto_scores.get)
    assert plan.algorithm.name == best
    assert plan.auto_scores[plan.algorithm.name] == min(
        plan.auto_scores.values())
    got = np.asarray(plan(a_h, b_h))
    np.testing.assert_allclose(got, a_d @ b, atol=1e-5)


def test_auto_choice_differs_with_sparsity_and_shape():
    """The cost model flips the schedule across operand regimes (the
    Bharadwaj-et-al observation auto-scheduling encodes).  No mesh is
    needed: auto_select scores plans abstractly, so 4x4 grids work
    in-process."""
    from repro.core.bsr import random_sparse
    # tiny, hypersparse A with a wide dense B: communication-dominated
    a_sp = TiledBSR.from_dense(random_sparse(64, 64, 0.05, seed=0),
                               ProcessGrid(4, 4), 8)
    comm_choice, comm_scores = api.auto_select(
        a_sp, jnp.ones((64, 512), jnp.float32))
    # huge dense x dense: compute-dominated
    comp_choice, comp_scores = api.auto_select(
        jnp.ones((4096, 4096), jnp.float32),
        jnp.ones((4096, 4096), jnp.float32), g=4)
    assert comm_choice != comp_choice
    for scores in (comm_scores, comp_scores):
        assert set(scores) == set(api.algorithms())
        assert all(s > 0 for s in scores.values())


def test_auto_select_respects_registration(operands):
    """A (temporarily) registered free-comm algorithm must win auto."""
    _, _, _, a_h, b_h, _ = operands
    ring_c = REGISTRY.get("ring_c")
    REGISTRY.register(Algorithm(
        name="freebie", body=ring_c.body, a_placement=ring_c.a_placement,
        b_placement=ring_c.b_placement, wire=(), wire_amortized=True))
    try:
        choice, scores = api.auto_select(a_h, b_h)
        assert "freebie" in scores
        assert scores["freebie"] == min(scores.values())
    finally:
        REGISTRY.unregister("freebie")


def test_bidir_with_unit_width_tiles(operands):
    """tn == 1 makes one bidir half-panel zero-width; the kernel wrapper
    must short-circuit n == 0 on every impl path."""
    a_d, _, _, a_h, _, _ = operands
    b_thin = np.random.default_rng(11).standard_normal(
        (16, 1)).astype(np.float32)
    for impl in ("ref", "interpret"):
        got = np.asarray(matmul(a_h, jnp.asarray(b_thin),
                                algorithm="ring_c_bidir", impl=impl))
        np.testing.assert_allclose(got, a_d @ b_thin, atol=1e-5)


def test_predicted_cost_positive(operands):
    _, _, _, a_h, b_h, _ = operands
    from repro.core.roofline import TPU_V5E
    for alg in api.algorithms():
        plan = plan_matmul(a_h, b_h, algorithm=alg, impl="ref")
        assert plan.predicted_cost(TPU_V5E) > 0


# ---------------------------------------------------------------------------
# Hot-loop hygiene: no coverage sort / B densification inside the scan
# ---------------------------------------------------------------------------
# The jaxpr-walk primitives live in repro.analysis.jaxpr_lint (shared
# with test_wire / test_overlap and the lint rules themselves).
def _scan_body_primitives(plan, a_h, b_h):
    from repro.analysis.jaxpr_lint import (scan_body_primitives, scan_eqns,
                                           trace_plan)
    jaxpr = trace_plan(plan, a_h, b_h)
    assert scan_eqns(jaxpr), \
        "expected a scanned ring loop in the plan executable"
    return scan_body_primitives(jaxpr)


@pytest.mark.parametrize("alg", ["ring_c", "ring_a", "ring_c_bidir"])
@pytest.mark.parametrize("kind", ["spmm", "spgemm"])
def test_scan_step_free_of_augment_and_densify(operands, alg, kind):
    """The scanned ring step must contain no coverage augmentation (sort /
    concatenate of the block lists) and no B-tile densification
    (scatter-add): both are hoisted to tiling / pre-scan time."""
    _, _, _, a_h, b_h, b_sph = operands
    rhs = b_h if kind == "spmm" else b_sph
    plan = plan_matmul(a_h, rhs, algorithm=alg, impl="interpret")
    prims = _scan_body_primitives(plan, a_h, rhs)
    offenders = {p for p in prims if "sort" in p or "scatter" in p}
    assert not offenders, (
        f"hot-loop bloat in {alg}/{kind} scan step: {sorted(offenders)}")


# ---------------------------------------------------------------------------
# API-hygiene guard (tools/check_api.py rides tier-1 via this test)
# ---------------------------------------------------------------------------
def _load_check_api():
    path = pathlib.Path(__file__).resolve().parents[1] / "tools" \
        / "check_api.py"
    spec = importlib.util.spec_from_file_location("check_api", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_examples_benchmarks_use_plan_api():
    assert _load_check_api().violations() == []


def test_check_api_flags_deprecated_import(tmp_path):
    (tmp_path / "examples").mkdir()
    (tmp_path / "benchmarks").mkdir()
    (tmp_path / "examples" / "bad.py").write_text(
        "from repro.core.spmm import spmm\n")
    found = _load_check_api().violations(str(tmp_path))
    assert len(found) == 1 and "bad.py" in found[0]


def test_check_api_flags_xla_flag_writes(tmp_path):
    """XLA_FLAGS has exactly one allowed write site
    (src/repro/runtime/platform.py); direct assignment or setdefault
    anywhere else is flagged."""
    (tmp_path / "examples").mkdir()
    (tmp_path / "src" / "repro" / "runtime").mkdir(parents=True)
    (tmp_path / "examples" / "bad.py").write_text(
        "import os\nos.environ['XLA_FLAGS'] = '--foo'\n")
    (tmp_path / "examples" / "bad2.py").write_text(
        "import os\nos.environ.setdefault('XLA_FLAGS', '--foo')\n")
    (tmp_path / "examples" / "ok.py").write_text(
        "from repro.runtime.platform import set_host_device_count\n"
        "set_host_device_count(4)\n")
    (tmp_path / "src" / "repro" / "runtime" / "platform.py").write_text(
        "import os\nos.environ['XLA_FLAGS'] = '--allowed-here'\n")
    found = _load_check_api().violations(str(tmp_path))
    assert len(found) == 2
    assert any("bad.py" in f for f in found)
    assert any("bad2.py" in f for f in found)


def test_check_api_flags_kernel_bypass(tmp_path):
    """examples/benchmarks must not bypass plan_matmul by importing the
    Pallas kernel module directly."""
    (tmp_path / "examples").mkdir()
    (tmp_path / "benchmarks").mkdir()
    (tmp_path / "benchmarks" / "bad1.py").write_text(
        "from repro.kernels.bsr_spmm import bsr_spmm_pallas\n")
    (tmp_path / "benchmarks" / "bad2.py").write_text(
        "from repro.kernels import bsr_spmm\n")
    (tmp_path / "benchmarks" / "ok.py").write_text(
        "from repro.kernels import ops\n")
    found = _load_check_api().violations(str(tmp_path))
    assert len(found) == 2
    assert any("bad1.py" in f for f in found)
    assert any("bad2.py" in f for f in found)


# ---------------------------------------------------------------------------
# Column balance (balance="cols"): B-side compensation + epilogue inversion
# ---------------------------------------------------------------------------
def _manual_cols_balanced_handle(d, block_size, seed=0):
    """A DistBSR carrying an explicit column-block permutation (the 1x1
    analogue of balance="cols"; see _manual_balanced_handle)."""
    import dataclasses
    nbc = d.shape[1] // block_size
    perm = np.random.default_rng(seed).permutation(nbc)
    dp = d.reshape(d.shape[0], nbc, block_size)[:, perm].reshape(d.shape)
    t = TiledBSR.from_dense(dp, ProcessGrid(1, 1), block_size)
    t = dataclasses.replace(t, col_block_perm=tuple(int(p) for p in perm))
    return DistBSR.from_tiled(t)


def test_balance_cols_shrinks_capacity_on_col_skew():
    d = _skewed_rmat().T.copy()              # hubs in columns
    none = TiledBSR.from_dense(d, ProcessGrid(4, 4), block_size=8)
    cols = TiledBSR.from_dense(d, ProcessGrid(4, 4), block_size=8,
                               balance="cols")
    assert cols.capacity < none.capacity
    assert cols.col_block_perm is not None and cols.row_block_perm is None
    # the balanced matrix is a pure column-block permutation of the original
    inv = np.argsort(np.asarray(cols.col_block_perm))
    back = np.asarray(cols.to_dense()).reshape(d.shape[0], -1, 8)[:, inv]
    np.testing.assert_array_equal(back.reshape(d.shape), d)


def test_balance_auto_picks_the_shrinking_axis():
    """Deterministically skewed inputs: all mass in a few row blocks ->
    auto picks rows; transposed -> cols; uniform -> identity."""
    d = np.zeros((64, 64), np.float32)
    d[:16, :] = 1.0                          # grid row 0 owns everything
    grid = ProcessGrid(4, 4)
    rowy = TiledBSR.from_dense(d, grid, 4, balance="auto")
    assert rowy.row_block_perm is not None and rowy.col_block_perm is None
    coly = TiledBSR.from_dense(d.T.copy(), grid, 4, balance="auto")
    assert coly.col_block_perm is not None and coly.row_block_perm is None
    uniform = TiledBSR.from_dense(np.ones((64, 64), np.float32), grid, 4,
                                  balance="auto")
    assert uniform.row_block_perm is None and uniform.col_block_perm is None


@pytest.mark.parametrize("alg", ["ring_c", "ring_a", "summa_bcast"])
def test_cols_balanced_left_operand_compensated(alg):
    """C = (A P)(P^T B) = A B: the planner permutes B's row blocks instead
    of touching the output (the ROADMAP's 'invert on B')."""
    d = _skewed_rmat()
    b = np.random.default_rng(4).standard_normal((256, 16)).astype(
        np.float32)
    h = _manual_cols_balanced_handle(d, 8)
    assert list(h.col_block_perm) != sorted(h.col_block_perm)
    got = np.asarray(matmul(h, jnp.asarray(b), algorithm=alg, impl="ref"))
    np.testing.assert_allclose(got, d @ b, atol=1e-3)


def test_cols_balanced_left_with_sparse_rhs(operands):
    a_d, _, b_sp, _, _, b_sph = operands
    a_bal = _manual_cols_balanced_handle(a_d, 4)
    got = np.asarray(matmul(a_bal, b_sph, algorithm="ring_c", impl="ref"))
    np.testing.assert_allclose(got, a_d @ b_sp, atol=1e-4)


def test_cols_balanced_right_operand_inverted_on_output(operands):
    """A cols-balanced RIGHT operand permutes C's column blocks; the
    shared epilogue inverts them before the crop."""
    a_d, _, b_sp, a_h, _, _ = operands
    b_bal = _manual_cols_balanced_handle(b_sp, 4)
    got = np.asarray(matmul(a_h, b_bal, algorithm="ring_c", impl="ref"))
    np.testing.assert_allclose(got, a_d @ b_sp, atol=1e-4)


def test_cols_balance_compensation_cached(operands):
    """The compensated right operand is materialized once per (handle,
    permutation), like placement states."""
    a_d, _, _, _, b_h, _ = operands
    a_bal = _manual_cols_balanced_handle(a_d, 4, seed=3)
    matmul(a_bal, b_h, algorithm="ring_c", impl="ref")
    comp = b_h._col_compensated[a_bal.col_block_perm]
    matmul(a_bal, b_h, algorithm="ring_c", impl="ref")
    assert b_h._col_compensated[a_bal.col_block_perm] is comp


def test_sparse_output_with_balance_fails_fast_and_actionably():
    """plan_matmul must reject balance= operands for sparse outputs up
    front, naming both workarounds (output="dense" / balance="none") —
    not with a generic error deep in plan construction (ISSUE-4
    satellite)."""
    d = _skewed_rmat()
    for h in (_manual_balanced_handle(d, 8),
              _manual_cols_balanced_handle(d, 8)):
        with pytest.raises(ValueError, match=r'output="dense"') as ei:
            plan_matmul(h, DistBSR.from_dense(d, g=G, block_size=8),
                        output="sparse")
        assert 'balance="none"' in str(ei.value)
    # auto degrades to a dense output instead of failing
    h = _manual_balanced_handle(d, 8)
    plan = plan_matmul(h, DistBSR.from_dense(d, g=G, block_size=8),
                       output="auto", algorithm="ring_c", impl="ref")
    assert plan.output == "dense"


def test_densify_inverts_balance_perms():
    d = _skewed_rmat()
    h_rows = _manual_balanced_handle(d, 8)
    h_cols = _manual_cols_balanced_handle(d, 8)
    np.testing.assert_array_equal(np.asarray(h_rows.densify()), d)
    np.testing.assert_array_equal(np.asarray(h_cols.densify()), d)


def test_from_tiled_balance_cols_roundtrip():
    d = _skewed_rmat().T.copy()
    plain = TiledBSR.from_dense(d, ProcessGrid(4, 4), block_size=8)
    h = DistBSR.from_tiled(plain, balance="cols", capacity=None)
    assert h.col_block_perm is not None
    assert h.capacity < plain.capacity
    np.testing.assert_array_equal(np.asarray(h.densify()), d)


def test_recommended_balance_follows_algorithm():
    assert api.recommended_balance("ring_a") == "cols"
    assert api.recommended_balance("ring_c") == "rows"
    assert api.recommended_balance("summa_bcast") == "rows"
    with pytest.raises(ValueError, match="unknown algorithm"):
        api.recommended_balance("cannon")


def test_check_api_flags_symbolic_outside_core(tmp_path):
    """core.symbolic is internal to repro/core: imports in examples or
    sibling src packages are flagged, core itself is allowed."""
    (tmp_path / "examples").mkdir()
    (tmp_path / "benchmarks").mkdir()
    pkg = tmp_path / "src" / "repro"
    (pkg / "kernels").mkdir(parents=True)
    (pkg / "core").mkdir()
    (pkg / "kernels" / "bad.py").write_text(
        "from repro.core.symbolic import symbolic_spgemm\n")
    (pkg / "core" / "ok.py").write_text(
        "from repro.core import symbolic\n")
    (tmp_path / "examples" / "bad2.py").write_text(
        "import repro.core.symbolic\n")
    found = _load_check_api().violations(str(tmp_path))
    assert len(found) == 2
    assert any("kernels" in f and "bad.py" in f for f in found)
    assert any("bad2.py" in f for f in found)
