"""Handles built straight onto their devices, and placements that move
tiles between devices.

* ``TiledBSR._scan_dense`` runs in numpy alone and returns exactly what
  the device round-trip scan it replaced returned (a copy of that scan is
  kept here as the oracle), reading an input that needs no padding in
  place.
* On four virtual CPU devices (a process of its own): tile (i, j) of a
  2x2 handle lives on device (i, j) of ``make_grid_mesh(2)`` from the
  start; placing an operand moves whole tiles, so no device ever holds
  more than two tiles of it; products on such handles match float64 numpy
  and a handle staged on one device; the dense-output plan gauges match
  hand counts, and ``plan.wire_bytes`` what the compiled program's
  collectives send (on four and on nine devices).
"""
import json
import os
import pathlib
import subprocess
import sys
import tracemalloc

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bsr import BSR, TiledBSR, random_sparse
from repro.core.grid import (ProcessGrid, bucket_capacity, ceil_div,
                             pad_to_multiple)
from repro.core.schedule import balance_row_perm


# --------------------------------------------------------------------------
# The scan as it was, block lists made on the default device (the oracle)
# --------------------------------------------------------------------------
def _old_bsr(dense, block_size, dtype=None):
    dense = np.asarray(dense)
    m, n = dense.shape
    mp, np_ = pad_to_multiple(m, block_size), pad_to_multiple(n, block_size)
    padded = np.zeros((mp, np_), dtype=dense.dtype)
    padded[:m, :n] = dense
    nbr, nbc = mp // block_size, np_ // block_size
    view = padded.reshape(nbr, block_size, nbc, block_size).transpose(
        0, 2, 1, 3)
    mask = np.abs(view).sum(axis=(2, 3)) != 0
    rr, cc = np.nonzero(mask)
    nnzb = len(rr)
    blocks = view[rr, cc]
    return BSR(jnp.asarray(blocks, dtype=dtype or dense.dtype),
               jnp.asarray(rr.astype(np.int32)),
               jnp.asarray(cc.astype(np.int32)),
               (mp, np_), block_size, nnzb, (m, n))


def _old_augment(blocks, rows, cols, n_block_rows):
    cov = np.arange(n_block_rows, dtype=rows.dtype)
    rows_aug = np.concatenate([rows, cov])
    order = np.argsort(rows_aug, kind="stable")
    bs = blocks.shape[1]
    blocks_aug = np.concatenate(
        [blocks, np.zeros((n_block_rows, bs, bs), blocks.dtype)])[order]
    cols_aug = np.concatenate(
        [cols, np.zeros((n_block_rows,), cols.dtype)])[order]
    return blocks_aug, rows_aug[order], cols_aug


def _old_scan(dense, grid, block_size, capacity, dtype, balance):
    dense = np.asarray(dense)
    m, n = dense.shape
    tm = pad_to_multiple(ceil_div(m, grid.rows), block_size)
    tn = pad_to_multiple(ceil_div(n, grid.cols), block_size)
    mp, np_ = tm * grid.rows, tn * grid.cols
    padded = np.zeros((mp, np_), dtype=dense.dtype)
    padded[:m, :n] = dense
    perm = col_perm = None
    if balance != "none":
        nbr_g, nbc_g = mp // block_size, np_ // block_size
        mask = np.abs(padded.reshape(nbr_g, block_size, nbc_g,
                                     block_size)).sum(axis=(1, 3)) != 0

        def tile_cap(mk):
            return int(mk.reshape(grid.rows, nbr_g // grid.rows, grid.cols,
                                  nbc_g // grid.cols).sum(axis=(1, 3)).max())

        best_cap, best_axis = tile_cap(mask), None
        if balance in ("rows", "auto"):
            p = balance_row_perm(mask.sum(axis=1), grid.rows)
            c = tile_cap(mask[np.asarray(p)])
            if c < best_cap:
                best_axis, best_cap, perm = "rows", c, p
        if balance in ("cols", "auto"):
            p = balance_row_perm(mask.sum(axis=0), grid.cols)
            c = tile_cap(mask[:, np.asarray(p)])
            if c < best_cap:
                best_axis, best_cap, col_perm = "cols", c, p
        if best_axis == "rows":
            col_perm = None
            padded = padded.reshape(nbr_g, block_size, np_)[perm].reshape(
                mp, np_)
            perm = tuple(int(p) for p in perm)
        elif best_axis == "cols":
            perm = None
            padded = padded.reshape(mp, nbc_g, block_size)[:, col_perm]
            padded = padded.reshape(mp, np_)
            col_perm = tuple(int(p) for p in col_perm)
        else:
            perm = col_perm = None
    tiles = [[_old_bsr(padded[i * tm:(i + 1) * tm, j * tn:(j + 1) * tn],
                       block_size, dtype=dtype)
              for j in range(grid.cols)] for i in range(grid.rows)]
    max_nnzb = max(t.nnzb for row in tiles for t in row)
    if capacity == "bucket":
        cap = bucket_capacity(max_nnzb)
    else:
        cap = capacity if capacity is not None else max_nnzb
    aug = [[_old_augment(np.asarray(t.blocks), np.asarray(t.rows),
                         np.asarray(t.cols), tm // block_size)
            for t in (u.with_capacity(cap) for u in row)] for row in tiles]
    blocks = np.stack([np.stack([a[0] for a in row]) for row in aug])
    rows_ = np.stack([np.stack([a[1] for a in row]) for row in aug])
    cols_ = np.stack([np.stack([a[2] for a in row]) for row in aug])
    counts = np.asarray([[t.nnzb for t in row] for row in tiles], np.int32)
    return blocks, rows_, cols_, counts, dict(
        shape=(mp, np_), block_size=block_size,
        grid_shape=(grid.rows, grid.cols), capacity=cap,
        logical_shape=(m, n), row_block_perm=perm, col_block_perm=col_perm)


def _skewed(n, seed):
    """Nonzeros crowded into a few block rows and columns, so a balance
    permutation shrinks the capacity."""
    d = random_sparse(n, n, 0.02, seed=seed)
    d[: n // 8] = random_sparse(n // 8, n, 0.3, seed=seed + 1)
    d[:, -n // 8:] += random_sparse(n, n // 8, 0.2, seed=seed + 2)
    return d


@pytest.mark.parametrize("capacity", [None, "bucket", 64],
                         ids=["min", "bucket", "int"])
@pytest.mark.parametrize("balance", ["none", "rows", "cols"])
@pytest.mark.parametrize("shape,grid", [((128, 128), (2, 2)),
                                        ((120, 100), (2, 2)),
                                        ((96, 112), (3, 3))],
                         ids=["unpadded", "padded", "padded3"])
def test_scan_matches_the_device_scan(shape, grid, balance, capacity):
    d = _skewed(128, seed=sum(shape) + len(balance))[:shape[0], :shape[1]]
    args = (ProcessGrid(*grid), 8, capacity, None, balance)
    got = TiledBSR._scan_dense(d, *args)
    want = _old_scan(d, *args)
    for g, w in zip(got[:4], want[:4]):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)
    assert got[4] == want[4]


@pytest.mark.parametrize("src,dtype", [(np.float64, None),
                                       (np.float32, jnp.bfloat16)],
                         ids=["f64-as-stored", "to-bf16"])
def test_scan_converts_as_the_device_did(src, dtype):
    d = _skewed(64, seed=5).astype(src)
    args = (ProcessGrid(2, 2), 8, "bucket", dtype, "none")
    got, want = TiledBSR._scan_dense(d, *args), _old_scan(d, *args)
    assert got[0].dtype == want[0].dtype
    np.testing.assert_array_equal(got[0], want[0])


def test_scan_reads_an_unpadded_input_in_place():
    """The scan's own allocations on an input that needs no padding stay
    well under the input's size: no padded copy, no full-size mask."""
    d = np.zeros((2048, 2048), np.float32)
    d[::97, ::89] = 1.0                       # a few hundred 32x32 blocks
    tracemalloc.start()
    try:
        out = TiledBSR._scan_dense(d, ProcessGrid(2, 2), 32, None, None,
                                   "none")
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert out[3].sum() > 0
    assert peak < 1.5 * d.nbytes


# --------------------------------------------------------------------------
# Four virtual devices
# --------------------------------------------------------------------------
_MESH_FACTS = r"""
import collections, gc, json
import numpy as np
from repro.runtime.platform import set_host_device_count
set_host_device_count(4)
import jax
from repro import obs
from repro.core import api
from repro.core.bsr import random_sparse
from repro.core.dist import make_grid_mesh
from repro.kernels.bsr_spmm import spmm_block_n

BS, N, W = 8, 128, 48
d = random_sparse(N, N, 0.06, seed=11)
d[:32, :40] = random_sparse(32, 40, 0.5, seed=12)     # an uneven tile
b = np.random.default_rng(3).standard_normal((N, W)).astype(np.float32)
mesh = make_grid_mesh(2)
pos = {dev.id: [int(i), int(j)] for (i, j), dev in np.ndenumerate(mesh.devices)}
out = {}

a = api.DistBSR.from_dense(d, g=2, block_size=BS)
out["tile_at"] = {str(pos[s.device.id]): [s.index[0].start, s.index[1].start]
                  for s in a.tiled.blocks.addressable_shards}
out["sharded"] = [str(x.sharding.spec) for x in
                  (a.tiled.blocks, a.tiled.rows, a.tiled.cols)]
out["counts"] = np.asarray(a.counts).tolist()
out["store"] = a.tiled.store_capacity
del a


def tiles_held(is_operand, tile_bytes):
    # an operand's distinct device buffers, in tiles, on the fullest device
    gc.collect()
    held, seen = collections.Counter(), set()
    for x in jax.live_arrays():
        if x.dtype != np.float32 or not is_operand(x.shape):
            continue
        pieces = [(next(iter(x.devices())), x)] if len(x.devices()) == 1 \
            else [(s.device, s.data) for s in x.addressable_shards]
        for dev, piece in pieces:
            key = (dev.id, piece.unsafe_buffer_pointer())
            if key not in seen:
                seen.add(key)
                held[dev.id] += piece.nbytes / tile_bytes
    return max(held.values())


a_tile = (lambda shape: shape[-2:] == (BS, BS), out["store"] * BS * BS * 4)
b_tile = (lambda shape: shape == (N, W), N // 2 * W // 2 * 4)
def held(alg, wire):
    api.clear_plan_cache()
    a = api.DistBSR.from_dense(d, g=2, block_size=BS)
    b_h = api.DistDense.for_rhs(b, a)
    rec = {"built": [tiles_held(*a_tile), tiles_held(*b_tile)]}
    plan = api.plan_matmul(a, b_h, algorithm=alg, impl="ref", wire=wire)
    plan._operands(a, b_h)              # each operand placed, on the mesh
    rec["placed"] = [tiles_held(*a_tile), tiles_held(*b_tile)]
    c = plan(a, b_h)
    c.block_until_ready()
    rec["err"] = float(np.abs(np.asarray(c, np.float64)
                              - d.astype(np.float64) @ b).max())
    del c
    rec["run"] = [tiles_held(*a_tile), tiles_held(*b_tile)]
    return rec


out["held"] = {f"{alg}.{wire}": held(alg, wire)
               for alg in ("ring_c", "ring_a", "summa_ag")
               for wire in ("padded", "packed")}

# the same products on a handle staged on one device, then committed
api.clear_plan_cache()
obs.enable(clear=True)
a = api.DistBSR.from_dense(d, g=2, block_size=BS)
dev0 = jax.devices()[0]
t = a.tiled
staged = api.DistBSR(type(t)(
    *(jax.device_put(np.asarray(x), dev0) for x in (t.blocks, t.rows,
                                                   t.cols, t.counts)),
    shape=t.shape, block_size=t.block_size, grid_shape=t.grid_shape,
    capacity=t.capacity, logical_shape=t.logical_shape))
out["products"] = {}
for impl in ("ref", "interpret"):
    api.clear_plan_cache()
    got = [np.asarray(api.plan_matmul(h, b_, algorithm="ring_c",
                                      impl=impl)(h, b_))
           for h, b_ in ((a, api.DistDense.for_rhs(b, a)),
                         (staged, jax.device_put(b, dev0)))]
    out["products"][impl] = {
        "err": float(np.abs(got[0].astype(np.float64)
                            - d.astype(np.float64) @ b).max()),
        "equal": bool((got[0] == got[1]).all())}
places = [e["args"] for e in obs.events() if e["name"] == "handle.place"]
out["places"] = places
obs.disable()

snap = obs.registry().snapshot()
out["gauges"] = {k: snap[k] for k in ("plan.spmm_block_steps",
                                      "plan.spmm_real_blocks",
                                      "plan.wire_bytes")}
out["panels"] = (W // 2) // spmm_block_n(W // 2)
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def facts():
    env = dict(os.environ, PYTHONPATH=str(pathlib.Path(__file__).resolve()
                                          .parents[1] / "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _MESH_FACTS], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_each_tile_is_built_on_its_own_device(facts):
    # mesh position (i, j) holds the shard that starts at tile (i, j)
    assert facts["tile_at"] == {str([i, j]): [i, j] for i in range(2)
                                for j in range(2)}
    assert set(facts["sharded"]) == {"PartitionSpec('row', 'col')"}


@pytest.mark.parametrize("alg", ["ring_c", "ring_a", "summa_ag"])
@pytest.mark.parametrize("wire", ["padded", "packed"])
def test_no_device_holds_more_than_two_tiles_of_an_operand(facts, alg,
                                                           wire):
    rec = facts["held"][f"{alg}.{wire}"]
    assert rec["built"] == [1.0, 1.0]
    for stage in ("placed", "run"):
        a_tiles, b_tiles = rec[stage]
        assert a_tiles <= 2.0 and b_tiles <= 2.0, (stage, rec)
    assert rec["err"] < 1e-4


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_products_on_mesh_handles_match_numpy_and_staged_handles(facts,
                                                                 impl):
    p = facts["products"][impl]
    assert p["err"] < 1e-4
    assert p["equal"]


def test_placements_record_the_bytes_they_move(facts):
    """ring_c's skews move half the tiles of each operand on a 2x2 grid:
    row 1 of A's tiles (blocks, rows, cols) and column 1 of B's."""
    bs, store, w = 8, facts["store"], 48
    a_tile = store * (bs * bs * 4 + 2 * 4)
    b_tile = 64 * (w // 2) * 4
    got = {(p["placement"], p["bytes_moved"]) for p in facts["places"]}
    assert got == {("skew_rows", 2 * a_tile), ("skew_cols", 2 * b_tile)}


@pytest.mark.parametrize("alg", ["ring_c", "ring_a", "summa_ag"])
def test_dense_output_gauges_match_hand_counts(facts, alg):
    """Every device multiplies g A lists of the stored length, a column
    panel of B at a time; the busiest device multiplies the real blocks
    of the tiles it walks: a grid row of A for ring_c and summa_ag, its
    own tile g times for ring_a."""
    counts = np.array(facts["counts"])
    g, panels = 2, facts["panels"]
    steps = g * facts["store"] * panels
    real = {"ring_c": counts.sum(axis=1).max(),
            "summa_ag": counts.sum(axis=1).max(),
            "ring_a": g * counts.max()}[alg] * panels
    gauges = facts["gauges"]
    label = f"algorithm={alg},wire=padded"
    assert gauges["plan.spmm_block_steps"][label] == steps
    assert gauges["plan.spmm_real_blocks"][label] == real
    # what the schedule sends: A (blocks, rows, cols) and B tiles g - 1
    # times around the ring or to the g - 1 peers of the all-gather; B
    # g - 1 times and the partial C g hops, the last one home, for ring_a
    a_tile = facts["store"] * (8 * 8 * 4 + 2 * 4)
    b_tile = 64 * 24 * 4
    c_tile = 64 * 24 * 4
    wire = {"ring_c": (g - 1) * (a_tile + b_tile),
            "summa_ag": (g - 1) * (a_tile + b_tile),
            "ring_a": (g - 1) * b_tile + g * c_tile}[alg]
    assert gauges["plan.wire_bytes"][label] == wire


_WIRE_SENT = r"""
import json, sys
import numpy as np
from repro.runtime.platform import set_host_device_count
g = int(sys.argv[1])
set_host_device_count(g * g)
from repro import obs
from repro.core import api
from repro.core.bsr import random_sparse
from repro.launch.hlo_analysis import analyze_hlo

n = 48 * g
d = random_sparse(n, n, 0.08, seed=1)
b = np.random.default_rng(0).standard_normal((n, 64)).astype(np.float32)
a = api.DistBSR.from_dense(d, g=g, block_size=8)
b_h = api.DistDense.for_rhs(b, a)
# bytes one device sends for each byte of a collective's operand: a ring
# shift sends its operand, an all-gather its operand to each of g - 1
# peers, a ring all-reduce 2 (g - 1) / g of it
per_byte = {"collective-permute": 1.0, "all-gather": g - 1.0,
            "all-reduce": 2.0 * (g - 1) / g}
out = {}


def sent_and_gauge(plan, a, b_h):
    stats = analyze_hlo(plan.lower(a, b_h).compile().as_text())
    sent = sum(v * per_byte[k]
               for k, v in stats.collective_bytes.items() if v)
    label = f"algorithm={plan.algorithm.name},wire={plan.wire}"
    return [sent, obs.registry().snapshot()["plan.wire_bytes"][label]]


for alg in api.algorithms():
    for wire in ("padded", "packed"):
        for overlap in ("off", "on"):
            plan = api.plan_matmul(a, b_h, algorithm=alg, impl="ref",
                                   wire=wire, overlap=overlap, cache=False)
            out[f"{alg}.{wire}.{overlap}"] = sent_and_gauge(plan, a, b_h)
# ring_c's A stream as its nonzero entries, on the default wire: an A of
# 32 x 32 blocks, one or two entries in each stored block
n_e = 256 * g
d_e = np.zeros((n_e, n_e), np.float32)
d_e[np.arange(0, n_e, 7), (np.arange(0, n_e, 7) * 13) % n_e] = 1.0
a_e = api.DistBSR.from_dense(d_e, g=g, block_size=32)
b_e = api.DistDense.for_rhs(
    np.random.default_rng(0).standard_normal((n_e, 64)).astype(np.float32),
    a_e)
for overlap in ("off", "on"):
    plan = api.plan_matmul(a_e, b_e, algorithm="ring_c", impl="ref",
                           overlap=overlap, cache=False)
    out[f"ring_c.{plan.wire}.{overlap}"] = sent_and_gauge(plan, a_e, b_e)
print(json.dumps(out))
"""


@pytest.mark.parametrize("g", [2, 3])
def test_wire_gauge_is_what_the_compiled_program_sends(g):
    """``plan.wire_bytes`` equals the bytes of the compiled program's
    collectives, loops counted by their trips, for every dense-output
    schedule, wire and overlap: the ring bodies send g - 1 shifts a
    stream, none that no step consumes; ``ring_c``'s A stream shipped as
    its entries sends their values and positions with rows and cols."""
    env = dict(os.environ, PYTHONPATH=str(pathlib.Path(__file__).resolve()
                                          .parents[1] / "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _WIRE_SENT, str(g)],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert len(out) == 26
    assert {"ring_c.entries.off", "ring_c.entries.on"} <= set(out)
    for key, (sent, gauge) in out.items():
        assert sent > 0 and gauge == pytest.approx(sent, rel=1e-12), key
