"""Pallas kernel validation: interpret-mode vs pure-jnp oracle, shape sweeps."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bsr import BSR, random_sparse
from repro.kernels import ops, ref


@pytest.mark.parametrize("m,k,n,bs,density", [
    (16, 16, 8, 8, 0.3),
    (32, 16, 16, 8, 0.15),
    (16, 32, 32, 16, 0.4),
    (24, 24, 8, 8, 0.0),       # empty matrix
    (16, 16, 8, 8, 1.0),       # dense
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bsr_spmm_interpret_matches_ref(m, k, n, bs, density, dtype):
    a_d = random_sparse(m, k, density, seed=m + k + n)
    b = np.random.default_rng(0).standard_normal((k, n)).astype(np.float32)
    a = BSR.from_dense(a_d, bs, capacity=None, dtype=dtype)
    b_j = jnp.asarray(b, dtype=dtype)
    want = np.asarray(a.to_dense().astype(jnp.float32)) @ np.asarray(
        b_j.astype(jnp.float32))
    got_ref = ops.bsr_spmm(a, b_j, impl="ref")
    got_pl = ops.bsr_spmm(a, b_j, impl="interpret", block_n=8)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got_ref, np.float32), want,
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(got_pl, np.float32), want,
                               rtol=tol, atol=tol)


def test_bsr_spmm_extra_capacity_padding():
    a_d = random_sparse(16, 16, 0.25, seed=2)
    b = np.random.default_rng(1).standard_normal((16, 8)).astype(np.float32)
    a = BSR.from_dense(a_d, 8).with_capacity(9)
    want = a_d @ b
    got = ops.bsr_spmm(a, jnp.asarray(b), impl="interpret", block_n=8)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mk,bs,da,db", [
    (16, 8, 0.4, 0.4),
    (32, 8, 0.15, 0.3),
    (16, 16, 1.0, 1.0),
])
def test_pair_matmul_spgemm_matches_dense(mk, bs, da, db):
    a_d = random_sparse(mk, mk, da, seed=4)
    b_d = random_sparse(mk, mk, db, seed=5)
    a = BSR.from_dense(a_d, bs)
    b = BSR.from_dense(b_d, bs)
    pa, pb, pr, pc, n_real = ops.build_pair_lists(
        a.rows, a.cols, a.nnzb, b.rows, b.cols, b.nnzb,
        a.n_block_rows, b.n_block_cols)
    want = a_d @ b_d
    for impl in ("ref", "interpret"):
        got = ops.bsr_pair_matmul(
            a.blocks, b.blocks, jnp.asarray(pa), jnp.asarray(pb),
            jnp.asarray(pr), jnp.asarray(pc),
            n_block_rows=a.n_block_rows, n_block_cols=b.n_block_cols,
            impl=impl)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


def test_pair_lists_cover_every_output_block():
    a_d = random_sparse(24, 24, 0.05, seed=9)
    a = BSR.from_dense(a_d, 8)
    pa, pb, pr, pc, _ = ops.build_pair_lists(
        a.rows, a.cols, a.nnzb, a.rows, a.cols, a.nnzb, 3, 3)
    covered = set(zip(pr.tolist(), pc.tolist()))
    assert covered == {(r, c) for r in range(3) for c in range(3)}


def test_match_block_pairs_join():
    """The extracted sort-merge join feeds both build_pair_lists and the
    distributed symbolic phase; check it against a brute-force join."""
    rng = np.random.default_rng(3)
    a_cols = rng.integers(0, 6, 20)
    b_rows = rng.integers(0, 6, 15)
    ai, bj = ops.match_block_pairs(a_cols, b_rows)
    want = {(i, j) for i in range(20) for j in range(15)
            if a_cols[i] == b_rows[j]}
    assert set(zip(ai.tolist(), bj.tolist())) == want
    assert (a_cols[ai] == b_rows[bj]).all()


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_pair_accumulate_packed_slots(impl):
    """Sparse-output SpGEMM inner: packed accumulation matches a dense
    scatter oracle, and slots visited only by coverage pairs come out
    exactly zero (the first-visit-zeroing contract)."""
    rng = np.random.default_rng(7)
    n_blocks, bs, n_slots = 12, 8, 5
    blocks_a = rng.standard_normal((n_blocks, bs, bs)).astype(np.float32)
    blocks_b = rng.standard_normal((n_blocks, bs, bs)).astype(np.float32)
    blocks_a[-1] = 0.0                      # a guaranteed zero slot each
    blocks_b[-1] = 0.0
    zero = n_blocks - 1
    # real pairs for slots {0, 2, 3}; slots 1 and 4 covered only by dummies
    pa = np.array([0, 1, zero, 2, 3, 4, zero, zero], np.int32)
    pb = np.array([1, 2, zero, 3, 4, 5, zero, zero], np.int32)
    ps = np.array([0, 0, 1, 2, 3, 3, 4, 4], np.int32)
    got = np.asarray(ops.bsr_pair_accumulate(
        jnp.asarray(blocks_a), jnp.asarray(blocks_b), jnp.asarray(pa),
        jnp.asarray(pb), jnp.asarray(ps), n_slots=n_slots, impl=impl))
    want = np.zeros((n_slots, bs, bs), np.float32)
    for a_i, b_i, s in zip(pa, pb, ps):
        want[s] += blocks_a[a_i] @ blocks_b[b_i]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert np.abs(got[1]).max() == 0.0 and np.abs(got[4]).max() == 0.0


@pytest.fixture
def pair_kernel_sizes(monkeypatch):
    """Set the pair-accumulate kernel's group and chunk for f32 8x8 blocks:
    ``set(group, chunk)`` sizes its operand ring and SMEM list budget to
    them.  JAX's caches are cleared around the test, so no other test runs
    the kernel at these sizes."""
    import jax
    from repro.kernels import bsr_spmm

    def set_sizes(group, chunk):
        monkeypatch.setattr(bsr_spmm, "PAIR_VMEM_BYTES",
                            group * 2 * 8 * 8 * (2 * 4 + 4))
        monkeypatch.setattr(bsr_spmm, "SMEM_LIST_BYTES", 3 * 4 * chunk)
        assert bsr_spmm.pair_group(8, jnp.float32) == group
        assert bsr_spmm.list_chunk(3) == chunk

    jax.clear_caches()
    yield set_sizes
    monkeypatch.undo()
    jax.clear_caches()


def _pair_lists(runs, n_blocks, seed):
    """Slot-sorted pair lists whose slot ``t`` has ``runs[t]`` entries."""
    rng = np.random.default_rng(seed)
    ps = np.repeat(np.arange(len(runs)), runs).astype(np.int32)
    pa = rng.integers(0, n_blocks, len(ps)).astype(np.int32)
    pb = rng.integers(0, n_blocks, len(ps)).astype(np.int32)
    return pa, pb, ps


# Slot runs against a 4-entry group; the grid steps start at 0, 4, 8, ...
@pytest.mark.parametrize("runs", [
    [3, 2, 4, 1],           # 10 entries: the last group takes 2
    [1, 2, 1],              # slot changes inside the one group
    [4, 4, 2],              # slot changes exactly at group boundaries
    [1, 10, 1],             # slot 1 spans three groups (entries 1-10)
    [1],                    # a one-entry list
], ids=["not_multiple", "inside_group", "at_boundary", "three_groups",
        "one_entry"])
def test_pair_accumulate_groups_match_ref(pair_kernel_sizes, runs):
    """Many list entries per grid step, slots changing anywhere in a
    group or across groups, match the oracle exactly as summed."""
    pair_kernel_sizes(group=4, chunk=64)
    rng = np.random.default_rng(len(runs))
    n_blocks, bs = 9, 8
    a = jnp.asarray(rng.standard_normal((n_blocks, bs, bs)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((n_blocks, bs, bs)), jnp.float32)
    pa, pb, ps = (jnp.asarray(x) for x in _pair_lists(runs, n_blocks, 1))
    got = ops.bsr_pair_accumulate(a, b, pa, pb, ps, n_slots=len(runs),
                                  impl="interpret")
    want = ref.bsr_pair_accumulate_raw_ref(a, b, pa, pb, ps, len(runs))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_pair_accumulate_three_chunks_match_ref(pair_kernel_sizes):
    """A list in the chunked layout of three chunks (built by the symbolic
    phase's chunker at a small chunk) runs as three kernel calls into one
    output; each chunk's last group is partial."""
    from repro.core import api

    pair_kernel_sizes(group=4, chunk=10)
    rng = np.random.default_rng(3)
    n_blocks, bs = 9, 8
    a_np = rng.standard_normal((n_blocks, bs, bs)).astype(np.float32)
    b_np = rng.standard_normal((n_blocks, bs, bs)).astype(np.float32)
    a_np[-1] = b_np[-1] = 0.0                   # the inert padding block
    runs = [3, 5, 2, 6, 1, 4, 3]
    pa, pb, ps = _pair_lists(runs, n_blocks - 1, 4)
    lists = api._symbolic.chunk_pair_lists(
        [(pa, pb, ps)], [(n_blocks - 1, n_blocks - 1)], 10)
    pa_c, pb_c, ps_c = (jnp.asarray(x[0]) for x in lists)
    assert pa_c.shape == (30,)
    a, b = jnp.asarray(a_np), jnp.asarray(b_np)
    got = ops.bsr_pair_accumulate(a, b, pa_c, pb_c, ps_c,
                                  n_slots=len(runs), impl="interpret")
    want = ref.bsr_pair_accumulate_raw_ref(
        a, b, jnp.asarray(pa), jnp.asarray(pb), jnp.asarray(ps), len(runs))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pair_accumulate_natural_group_matches_ref(dtype):
    """At the group the block shape gives (64 for 8x8 blocks), a list of
    three groups and a part, slots of 1 to 70 entries."""
    from repro.kernels.bsr_spmm import pair_group

    assert pair_group(8, dtype) == 64
    rng = np.random.default_rng(5)
    n_blocks, bs = 16, 8
    a = jnp.asarray(rng.standard_normal((n_blocks, bs, bs)), dtype)
    b = jnp.asarray(rng.standard_normal((n_blocks, bs, bs)), dtype)
    runs = [1, 70, 5, 63, 64, 2, 9]
    pa, pb, ps = (jnp.asarray(x) for x in _pair_lists(runs, n_blocks, 6))
    assert 3 * 64 < ps.shape[0] < 4 * 64
    got = ops.bsr_pair_accumulate(a, b, pa, pb, ps, n_slots=len(runs),
                                  out_dtype=jnp.float32, impl="interpret")
    want = ref.bsr_pair_accumulate_raw_ref(a, b, pa, pb, ps, len(runs))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
