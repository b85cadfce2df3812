"""Symbolic/numeric SpGEMM tests.

The symbolic phase is pure host-side numpy, so its structural properties
(predicted mask == dense-product mask) are checked on real multi-tile
grids in-process; numeric sparse-output execution runs on the g=1 mesh
(multi-device grids are covered by ``selftest --check spgemm_sparse`` via
``tests/test_distributed.py``).  Also home to the capacity-bucketed
plan-cache test and the ``tools/fit_machine.py`` recovery test.
"""
import dataclasses
import importlib.util
import pathlib

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import api
from repro.core.api import DistBSR, DistDense, matmul, plan_matmul
from repro.core.bsr import TiledBSR, random_sparse, rmat_matrix
from repro.core.grid import ProcessGrid, bucket_capacity

G = 1  # the main pytest process owns a single CPU device


def _tiled_pair(kind: str, g: int, bs: int):
    if kind == "rmat":
        a_d = rmat_matrix(scale=6, edgefactor=4, seed=1)
        b_d = rmat_matrix(scale=6, edgefactor=4, seed=2)
    else:
        a_d = random_sparse(48, 48, 0.12, seed=3)
        b_d = random_sparse(48, 48, 0.2, seed=4)
    grid = ProcessGrid(g, g)
    return (a_d, b_d, TiledBSR.from_dense(a_d, grid, bs),
            TiledBSR.from_dense(b_d, grid, bs))


def _block_mask(d, shape, bs):
    """Block mask of a matrix on the padded grid."""
    padded = np.zeros(shape)
    padded[:d.shape[0], :d.shape[1]] = np.abs(d)
    nbr, nbc = shape[0] // bs, shape[1] // bs
    return padded.reshape(nbr, bs, nbc, bs).sum(axis=(1, 3)) != 0


# ---------------------------------------------------------------------------
# Symbolic phase: structural properties (host-side, any grid size)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", ["random", "rmat"])
@pytest.mark.parametrize("g,bs", [(1, 4), (2, 4), (4, 8)])
def test_predicted_mask_is_block_product_and_covers_result(kind, g, bs):
    """The predicted structure equals the boolean product of the operands'
    block masks — the exact block-granularity structure — and therefore
    covers the true product's mask (block structure is an upper bound:
    two nonzero blocks whose scalar supports don't align multiply to a
    zero block, which R-MAT inputs exercise)."""
    a_d, b_d, a_t, b_t = _tiled_pair(kind, g, bs)
    sym = api.symbolic_spgemm(a_t, b_t)
    a_shape, b_shape = a_t.shape, b_t.shape
    a_mask = _block_mask(a_d, a_shape, bs)
    b_mask = _block_mask(b_d, b_shape, bs)
    want = (a_mask.astype(int) @ b_mask.astype(int)) > 0
    got = sym.block_mask()
    np.testing.assert_array_equal(got, want)
    assert int(sym.c_counts.sum()) == int(want.sum())
    assert sym.density() == pytest.approx(want.mean())
    # no false negatives vs the actual product (abs: no cancellation)
    true_mask = _block_mask(np.abs(a_d) @ np.abs(b_d),
                            (a_shape[0], b_shape[1]), bs)
    assert (got | true_mask == got).all()


@pytest.mark.parametrize("kind", ["random", "rmat"])
def test_predicted_density_prefix_matches_full_phase(kind):
    """The structure-only density (what output="auto" consults — no pair
    lists built) must equal the full symbolic phase's density exactly."""
    _, _, a_t, b_t = _tiled_pair(kind, 2, 4)
    sym = api.symbolic_spgemm(a_t, b_t)
    assert api.predicted_density(a_t, b_t) == sym.density()


def test_symbolic_layout_satisfies_storage_contract():
    """The predicted C layout must satisfy the TiledBSR storage contract
    (row-sorted, every block-row covered, uniform store capacity) so the
    numeric result chains straight into further multiplies."""
    _, _, a_t, b_t = _tiled_pair("rmat", 2, 4)
    sym = api.symbolic_spgemm(a_t, b_t)
    assert sym.store_capacity == sym.capacity + sym.tile_nbr
    assert sym.capacity == bucket_capacity(int(sym.c_counts.max()))
    for i in range(sym.g):
        for j in range(sym.g):
            rows = sym.c_rows[i, j]
            assert (np.diff(rows) >= 0).all()
            assert set(rows.tolist()) == set(range(sym.tile_nbr))


def test_symbolic_pair_lists_sorted_and_covering():
    """Pair slots are nondecreasing and every output slot is visited (the
    packed kernel's first-visit-zeroing contract)."""
    _, _, a_t, b_t = _tiled_pair("random", 2, 4)
    sym = api.symbolic_spgemm(a_t, b_t)
    for i in range(sym.g):
        for j in range(sym.g):
            for k in range(sym.g):
                ps = sym.pair_slot[i, j, k]
                assert (np.diff(ps) >= 0).all()
                assert set(ps.tolist()) == set(range(sym.store_capacity))


def test_symbolic_validates_operands():
    grid = ProcessGrid(2, 2)
    a4 = TiledBSR.from_dense(random_sparse(32, 32, 0.2, seed=0), grid, 4)
    a8 = TiledBSR.from_dense(random_sparse(32, 32, 0.2, seed=0), grid, 8)
    small = TiledBSR.from_dense(random_sparse(16, 16, 0.2, seed=0), grid, 4)
    with pytest.raises(ValueError, match="block size"):
        api.symbolic_spgemm(a4, a8)
    with pytest.raises(ValueError, match="inner"):
        api.symbolic_spgemm(a4, small)
    with pytest.raises(ValueError, match="capacity"):
        api.symbolic_spgemm(a4, a4, capacity=1)


# ---------------------------------------------------------------------------
# Numeric sparse output (g=1 mesh; multi-device in selftest)
# ---------------------------------------------------------------------------
@pytest.fixture
def sparse_operands():
    a_d = random_sparse(16, 16, 0.15, seed=0)
    b_d = random_sparse(16, 16, 0.25, seed=1)
    a_h = DistBSR.from_dense(a_d, g=G, block_size=4)
    b_h = DistBSR.from_dense(b_d, g=G, block_size=4)
    return a_d, b_d, a_h, b_h


@pytest.mark.parametrize("alg", api.sparse_algorithms())
@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_sparse_output_allclose_dense_output(sparse_operands, alg, impl):
    a_d, b_d, a_h, b_h = sparse_operands
    plan = plan_matmul(a_h, b_h, algorithm=alg, impl=impl, output="sparse")
    assert plan.kind == "spgemm" and plan.output == "sparse"
    c = plan(a_h, b_h)
    assert isinstance(c, DistBSR)
    assert c.logical_shape == (16, 16)
    dense = np.asarray(matmul(a_h, b_h, algorithm=alg, impl=impl))
    np.testing.assert_allclose(np.asarray(c.densify()), dense, atol=1e-5)
    np.testing.assert_allclose(np.asarray(c.densify()), a_d @ b_d,
                               atol=1e-5)


@pytest.fixture
def small_smem(monkeypatch):
    """Shrink the kernels' SMEM list budget to 16 entries per list (three
    lists) so small products take the chunked multi-call path."""
    import jax
    from repro.kernels import bsr_spmm
    monkeypatch.setattr(bsr_spmm, "SMEM_LIST_BYTES", 3 * 4 * 16)
    api.clear_plan_cache()
    jax.clear_caches()
    yield 16
    api.clear_plan_cache()
    jax.clear_caches()


def test_chunk_pair_lists_cut_only_at_slot_boundaries():
    """Long lists become equal chunks that each start at a new slot, keep
    the list slot-sorted, and pad with inert pairs on the chunk's last
    slot; short lists keep the bucketed single-call layout."""
    chunk_pair_lists = api._symbolic.chunk_pair_lists
    rng = np.random.default_rng(0)
    lists, pads = [], []
    for n_slots in (30, 7, 41):
        ps = np.repeat(np.arange(n_slots), rng.integers(1, 5, n_slots))
        lists.append((np.arange(len(ps)) + 100, np.arange(len(ps)) + 200,
                      ps))
        pads.append((-1, -2))
    pa, pb, ps = chunk_pair_lists(lists, pads, chunk=16)
    assert pa.shape[1] % 16 == 0 and pa.shape[1] > 16
    for row, (la, lb, lps) in enumerate(lists):
        assert (np.diff(ps[row]) >= 0).all()
        chunks = ps[row].reshape(-1, 16)
        real = (pa[row] >= 0).reshape(-1, 16)
        for t in range(1, len(chunks)):
            assert chunks[t][0] > chunks[t - 1][-1]   # no slot spans two
        assert real[:, 0].all()                       # no empty chunk
        np.testing.assert_array_equal(pa[row][pa[row] >= 0], la)
        np.testing.assert_array_equal(pb[row][pb[row] >= 0], lb)
        np.testing.assert_array_equal(ps[row][pa[row] >= 0], lps)
        assert (pb[row][pa[row] < 0] == -2).all()
    short = chunk_pair_lists(lists[1:2], pads[1:2], chunk=64)
    assert short[0].shape[1] == bucket_capacity(len(lists[1][0]))
    with pytest.raises(ValueError, match="more than 3 pairs"):
        chunk_pair_lists(lists, pads, chunk=3)


@pytest.mark.parametrize("alg", api.sparse_algorithms())
def test_chunked_sparse_output_interpret_matches_ref(small_smem,
                                                     sparse_operands, alg):
    """Pair lists past the SMEM budget run as several kernel calls into
    one output buffer and still match the ref path and the dense product."""
    a_d, b_d, a_h, b_h = sparse_operands
    plan = plan_matmul(a_h, b_h, algorithm=alg, impl="interpret",
                       output="sparse")
    cap = plan.symbolic.pair_capacity
    assert cap > small_smem and cap % small_smem == 0
    got = np.asarray(plan(a_h, b_h).densify())
    want = np.asarray(matmul(a_h, b_h, algorithm=alg, impl="ref",
                             output="sparse").densify())
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(got, a_d @ b_d, atol=1e-5)


def test_unchunked_long_spmm_list_is_refused(small_smem):
    """A stored tile longer than the SMEM budget is refused before Mosaic
    would run out of SMEM, with the budget in the message."""
    from repro.core.bsr import BSR
    from repro.kernels import ops
    a = BSR.from_dense(random_sparse(64, 64, 0.5, seed=0), 4)
    assert a.capacity > 24
    with pytest.raises(ValueError, match="SMEM budget"):
        ops.bsr_spmm(a, jnp.ones((64, 8), jnp.float32), impl="interpret",
                     block_n=8)


def test_chained_cube_stays_packed(sparse_operands):
    """A @ A @ A chains through DistBSR handles — no densify, no re-tile —
    and the product handle works as either operand."""
    a_d, _, a_h, _ = sparse_operands
    c2 = matmul(a_h, a_h, algorithm="ring_c", impl="ref", output="sparse")
    c3 = matmul(c2, a_h, algorithm="ring_c", impl="ref", output="sparse")
    assert isinstance(c2, DistBSR) and isinstance(c3, DistBSR)
    np.testing.assert_allclose(np.asarray(c3.densify()), a_d @ a_d @ a_d,
                               atol=1e-4)
    c3r = matmul(a_h, c2, algorithm="ring_c", impl="ref", output="sparse")
    np.testing.assert_allclose(np.asarray(c3r.densify()),
                               np.asarray(c3.densify()), atol=1e-4)


def test_sparse_plan_traces_once_and_caches(sparse_operands):
    _, _, a_h, b_h = sparse_operands
    api.clear_plan_cache()
    plan = plan_matmul(a_h, b_h, algorithm="ring_c", impl="ref",
                       output="sparse")
    for _ in range(4):
        plan(a_h, b_h)
    assert plan.traces == 1
    assert plan_matmul(a_h, b_h, algorithm="ring_c", impl="ref",
                       output="sparse") is plan
    # the dense-output plan for the same operands is a different plan
    dense_plan = plan_matmul(a_h, b_h, algorithm="ring_c", impl="ref")
    assert dense_plan is not plan and dense_plan.output == "dense"


def test_sparse_plan_guards_structure(sparse_operands):
    """Pair lists are baked per structure: same abstract shapes but a
    different sparsity pattern must not silently reuse the executable."""
    a_d, b_d, a_h, b_h = sparse_operands
    plan = plan_matmul(a_h, b_h, algorithm="ring_c", impl="ref",
                       output="sparse")
    other = DistBSR.from_dense(random_sparse(16, 16, 0.15, seed=9), g=G,
                               block_size=4,
                               capacity=a_h.capacity)  # same abstract key
    assert other.abstract_key() == a_h.abstract_key()
    with pytest.raises(ValueError, match="structure"):
        plan(other, b_h)
    plan2 = plan_matmul(other, b_h, algorithm="ring_c", impl="ref",
                        output="sparse")
    assert plan2 is not plan   # structure key separates the cache entries


def test_output_auto_picks_by_predicted_density():
    hyper = DistBSR.from_dense(random_sparse(512, 512, 0.0008, seed=3),
                               g=G, block_size=8)
    p = plan_matmul(hyper, hyper, impl="ref", output="auto")
    assert p.output == "sparse"
    densish = DistBSR.from_dense(random_sparse(16, 16, 0.6, seed=4), g=G,
                                 block_size=4)
    p2 = plan_matmul(densish, densish, impl="ref", output="auto")
    assert p2.output == "dense"
    # threshold override flips the decision
    p3 = plan_matmul(densish, densish, impl="ref", output="auto",
                     sparse_threshold=1.0)
    assert p3.output == "sparse"
    # spmm (dense rhs) silently stays dense under "auto"
    b = DistDense.for_rhs(jnp.ones((16, 8), jnp.float32), densish)
    assert plan_matmul(densish, b, impl="ref", output="auto").output \
        == "dense"
    # an explicitly requested dense-only algorithm keeps auto on dense
    p4 = plan_matmul(hyper, hyper, algorithm="ring_a", impl="ref",
                     output="auto")
    assert p4.output == "dense" and p4.algorithm.name == "ring_a"


def test_sparse_output_validation(sparse_operands):
    _, _, a_h, b_h = sparse_operands
    with pytest.raises(ValueError, match="DistBSR"):
        plan_matmul(a_h, jnp.ones((16, 8), jnp.float32), output="sparse")
    other_bs = DistBSR.from_dense(random_sparse(16, 16, 0.2, seed=5), g=G,
                                  block_size=8)
    with pytest.raises(ValueError, match="block size"):
        plan_matmul(a_h, other_bs, output="sparse")
    with pytest.raises(ValueError, match="sparse-output body"):
        plan_matmul(a_h, b_h, algorithm="ring_a", output="sparse")
    with pytest.raises(ValueError, match="output"):
        plan_matmul(a_h, b_h, output="packed")


def test_sparse_rejects_balanced_operands():
    d = rmat_matrix(scale=6, edgefactor=8, seed=2)
    nbr = d.shape[0] // 4
    perm = np.random.default_rng(0).permutation(nbr)
    dp = d.reshape(nbr, 4, -1)[perm].reshape(d.shape)
    t = dataclasses.replace(
        TiledBSR.from_dense(dp, ProcessGrid(1, 1), 4),
        row_block_perm=tuple(int(p) for p in perm))
    bal = DistBSR.from_tiled(t)
    plain = DistBSR.from_dense(d, g=G, block_size=4)
    with pytest.raises(ValueError, match="balance"):
        plan_matmul(bal, plain, output="sparse")


def test_sparse_cost_model_charges_packed_output(sparse_operands):
    """auto_select(output='sparse') scores only sparse-capable schedules,
    against B-stays-sparse wire traffic and packed C bytes."""
    _, _, a_h, b_h = sparse_operands
    choice, scores = api.auto_select(a_h, b_h, output="sparse")
    assert set(scores) == set(api.sparse_algorithms())
    assert choice == min(scores, key=scores.get)
    # hypersparse operands: the sparse model must charge less wire (B rides
    # packed blocks, no densified tile) and fewer executed flops
    hyper = DistBSR.from_dense(random_sparse(512, 512, 0.0008, seed=3),
                               g=G, block_size=8)
    sparse_plan = plan_matmul(hyper, hyper, algorithm="ring_c", impl="ref",
                              output="sparse")
    dense_plan = plan_matmul(hyper, hyper, algorithm="ring_c", impl="ref")
    cm_s, cm_d = sparse_plan.cost_model(), dense_plan.cost_model()
    assert cm_s["net_bytes_per_step"] < cm_d["net_bytes_per_step"]
    assert cm_s["flops_per_step"] < cm_d["flops_per_step"]
    sym = sparse_plan.symbolic
    assert sym.flops() <= 2 * sym.pair_capacity * sym.block_size ** 3 \
        * sym.g ** 3


# ---------------------------------------------------------------------------
# Capacity-bucketed plan cache (satellite)
# ---------------------------------------------------------------------------
def test_bucket_capacity_series():
    # 0 is its own bucket: an empty operand must not allocate phantom
    # block storage (ISSUE-4 satellite)
    assert bucket_capacity(0) == 0
    assert bucket_capacity(1) == 1
    for c in (3, 17, 146, 150, 705):
        b = bucket_capacity(c)
        assert b >= c and b <= max(2, int(np.ceil(c * 1.25)))
    # values inside one bucket gap coincide (the plan-sharing property)
    assert bucket_capacity(170) == bucket_capacity(185) == 185
    assert bucket_capacity(149) == bucket_capacity(150)
    with pytest.raises(ValueError):
        bucket_capacity(-1)


def test_bucketed_handles_share_one_plan_and_trace():
    """Near-identical sparsity patterns (capacities 246..253 minimal) round
    up to one bucket, so their plans — and the jitted executable — are
    shared: one trace total across both matrices."""
    h1 = DistBSR.from_dense(random_sparse(64, 64, 0.2, seed=0), g=G,
                            block_size=4)
    h2 = DistBSR.from_dense(random_sparse(64, 64, 0.2, seed=1), g=G,
                            block_size=4)
    exact1 = DistBSR.from_dense(random_sparse(64, 64, 0.2, seed=0), g=G,
                                block_size=4, capacity=None)
    assert h1.capacity == h2.capacity > exact1.capacity
    assert h1.abstract_key() == h2.abstract_key()
    b = DistDense.for_rhs(jnp.ones((64, 8), jnp.float32), h1)
    api.clear_plan_cache()
    seen = []
    hook = api.add_trace_hook(lambda plan: seen.append(plan))
    try:
        p1 = api.plan_matmul(h1, b, algorithm="ring_c", impl="ref")
        p1(h1, b)
        p2 = api.plan_matmul(h2, b, algorithm="ring_c", impl="ref")
        p2(h2, b)
    finally:
        api.remove_trace_hook(hook)
    assert p1 is p2
    assert len(seen) == 1 and p1.traces == 1
    assert api.plan_cache_size() == 1


# ---------------------------------------------------------------------------
# Machine fitting (tools/fit_machine.py satellite)
# ---------------------------------------------------------------------------
def _load_fit_machine():
    path = pathlib.Path(__file__).resolve().parents[1] / "tools" \
        / "fit_machine.py"
    spec = importlib.util.spec_from_file_location("fit_machine", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_fit_machine_recovers_synthetic_constants():
    """Generate measured times from a known Machine via the cost model
    itself; the least-squares fit must recover its net constants."""
    import dataclasses as dc

    from repro.core.roofline import TPU_V5E

    fm = _load_fit_machine()
    true = dc.replace(TPU_V5E, net_bw=7.5e9, hop_latency=3e-5)
    a_h = DistBSR.from_dense(random_sparse(128, 128, 0.1, seed=5), g=G,
                             block_size=8)
    records = []
    for n_cols in (32, 256, 1024):
        b_h = DistDense.for_rhs(jnp.ones((128, n_cols), jnp.float32), a_h)
        geom = api._geometry(a_h, b_h, impl=None, axis_row="row",
                             axis_col="col")
        for name in api.algorithms():
            alg = api.REGISTRY.get(name)
            if alg.cost_fn is not None:  # steal3d: structure-dependent
                continue                 # cost, not the generic model
            cm = api._cost_model(alg, geom, a_h.abstract_key(),
                                 b_h.abstract_key())
            records.append({"cm": cm, "alg": alg, "source": name,
                            "measured": api._predicted_time(cm, alg, true)})
    # bsp records are exactly linear in the unknowns; rings only when
    # comm-bound — fit() drops the rest
    fitted, diag = fm.fit(records, TPU_V5E)
    assert fitted.net_bw == pytest.approx(true.net_bw, rel=0.05)
    assert fitted.hop_latency == pytest.approx(true.hop_latency, rel=0.05)
    assert diag["n_used"] >= 2


def test_fit_machine_roundtrips_preset(tmp_path):
    from repro.core import roofline
    m = roofline.Machine("probe", 1e12, 1e11, 1e9, 4, 2e-6)
    path = str(tmp_path / "machine.json")
    roofline.save_machine(m, path)
    assert roofline.load_machine(path) == m
