"""The element codec (``repro.core.entries``) and the ``ring_c`` plans that
ship A's tile as its nonzero entries.

* ``encode`` then ``decode`` gives back every tile bit for bit, whether
  its entries and padding fit one of the encoder's chunks or not:
  ``-0.0``, NaN, inf and subnormals ride as entries, ``+0.0`` is the
  decode's fill, and the padding entries past a tile's count are
  dropped.
* The rule: a tile ships as entries when shipping and decoding them beats
  the exposed shift of its blocks by the costs measured on a v5e (below
  about 0.6% of a float32 tile's positions), its blocks are at most 256
  wide and every position fits int32; the encoder refuses wider blocks;
  the capacity is the bucketed largest count.
* A g = 1 plan and a sparse-output plan never count entries.
* On 4 and 9 virtual CPU devices (a process of its own each): a ``ring_c``
  dense-output product with the codec engaged equals ``wire="padded"`` on
  the same handles bit for bit, for an R-MAT A holding ``-0.0``, a NaN, an
  inf, an all-padding tile and a nearly dense hub block (at g = 3 the
  encoded tile is forwarded round the ring); ``plan.wire_entries`` reads
  the entries shipped for that A and 0 for a block-dense one, whose plan
  keeps blocks, as do an A whose blocks are a tenth full and an A of 512
  x 512 blocks; a plan refuses a handle with more entries in a tile than
  its capacity; no device holds more than two tiles of A's blocks.
"""
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api, entries
from repro.kernels.ref import entry_decode_ref
from repro.core.bsr import random_sparse
from repro.core.grid import bucket_capacity


def _tiles(case: str) -> np.ndarray:
    """A ``[2, 2, 3, 4, 4]`` float32 stack of tiles for ``case``; for
    ``"wide"`` a ``[1, 2, 40, 32, 32]`` one, with slots of more than 255
    entries and more slots than a block has rows; for ``"many"`` a ``[1,
    1, 24, 32, 32]`` one of more entries than the encoder places at a
    time."""
    rng = np.random.default_rng(7)
    shape = {"wide": (1, 2, 40, 32, 32),
             "many": (1, 1, 24, 32, 32)}.get(case, (2, 2, 3, 4, 4))
    t = np.where(rng.random(shape) < 0.3,
                 rng.standard_normal(shape), 0).astype(np.float32)
    if case == "specials":
        t[0, 0, 0, 0, :4] = [-0.0, np.nan, np.inf, -np.inf]
        t[0, 1, 2, 3, 3] = np.float32(1e-45)            # a subnormal
        t[1, 1] = 0.0                                   # a tile of padding
        t[1, 0, 1] = rng.random((4, 4)) + 1.0           # a dense block
    elif case == "empty":
        t[:] = 0.0
    elif case == "dense":
        t = rng.random(t.shape).astype(np.float32) + 1.0
    elif case == "wide":
        t[0, 1, 33] = rng.random((32, 32)) + 1.0        # 1,024 entries
        t[0, 0, 5:9] = 0.0
    elif case == "many":
        t = np.where(rng.random(shape) < 0.75, t + 2.0, 0).astype(np.float32)
        assert (t != 0).sum() > entries.ENCODE_CHUNK
    return t


def _bits(x) -> np.ndarray:
    return np.asarray(x).view(np.uint32)


@pytest.mark.parametrize("case", ["specials", "empty", "dense", "sparse",
                                  "wide", "many"])
@pytest.mark.parametrize("spare", [0, 5, entries.ENCODE_CHUNK + 3])
def test_decode_returns_every_tile_bit_for_bit(case, spare):
    t = _tiles(case)
    counts = entries.count(jnp.asarray(t), None)
    assert counts.tolist() == (_bits(t) != 0).sum(axis=(2, 3, 4)).tolist()
    cap = max(int(counts.max()), 1) + spare
    enc = entries.encode(jnp.asarray(t), None, cap)
    assert enc["vals"].shape == enc["idx"].shape == t.shape[:2] + (cap,)
    assert enc["idx"].dtype == jnp.int32
    idx = np.asarray(enc["idx"])
    for i in range(t.shape[0]):
        for j in range(t.shape[1]):
            n = int(counts[i, j])
            # real entries first, at ascending distinct positions in the
            # tile; padding entries 0, past the tile's end
            assert (np.diff(idx[i, j]) > 0).all()
            assert (idx[i, j, :n] < t[i, j].size).all()
            assert (idx[i, j, n:] >= t[i, j].size).all()
            assert (_bits(enc["vals"][i, j, n:]) == 0).all()
            got = entry_decode_ref(enc["vals"][i, j], enc["idx"][i, j],
                                   t.shape[2:])
            np.testing.assert_array_equal(_bits(got), _bits(t[i, j]))


def test_capacity_and_rule():
    assert entries.entry_capacity(0) == 1
    for n in (1, 7, 1000, 287_175):
        cap = entries.entry_capacity(n)
        assert cap >= n and cap == bucket_capacity(n)
    # the 2x2 cell's fullest tile: 294,187 entries (8 B each) against
    # 39,740 stored 128 x 128 float32 blocks (0.045% of the positions)
    assert entries.entry_bytes(294_187, 4) == 294_187 * 8
    assert entries.entries_win(294_187, 39_740, 128, 4)
    # the break-even by the measured costs lies near 0.57% of a float32
    # tile's positions, far below half the blocks' bytes
    positions = 39_740 * 128 * 128
    assert entries.entries_win(int(positions * 0.0055), 39_740, 128, 4)
    assert not entries.entries_win(int(positions * 0.006), 39_740, 128, 4)
    assert not entries.entries_win(positions // 10, 39_740, 128, 4)
    assert not entries.entries_win(positions // 16 - 1, 39_740, 128, 4)
    # blocks wider than the encoder counts exactly keep blocks
    assert entries.entries_win(10, 1_000, 256, 4)
    assert not entries.entries_win(10, 1_000, 512, 4)
    # positions that would pass int32 keep blocks
    assert not entries.entries_win(10, 2 ** 31 // (128 * 128), 128, 4)


def test_encoder_refuses_blocks_wider_than_it_counts():
    t = jnp.zeros((1, 1, 1, 512, 512), jnp.float32)
    with pytest.raises(ValueError, match="block size 512"):
        entries.encode(t, None, 4)


def test_single_tile_and_sparse_output_plans_count_no_entries():
    d = np.abs(random_sparse(64, 64, 0.05, seed=3))
    b = np.random.default_rng(1).standard_normal((64, 16)).astype(np.float32)
    a = api.DistBSR.from_dense(d, g=1, block_size=8)
    plan = api.plan_matmul(a, api.DistDense.for_rhs(b, a), algorithm="ring_c",
                           impl="ref", cache=False)
    assert plan.wire == "padded"
    np.testing.assert_allclose(np.asarray(plan(a, api.DistDense.for_rhs(
        b, a))), d @ b, rtol=1e-5, atol=1e-5)
    sp = api.plan_matmul(a, a, algorithm="ring_c", output="sparse",
                         impl="ref", cache=False)
    assert sp.wire == "packed"
    assert a._entry_counts is None


_MESH = r"""
import collections, gc, json, sys
import numpy as np
from repro.runtime.platform import set_host_device_count
g = int(sys.argv[1])
set_host_device_count(g * g)
import jax
from repro import obs
from repro.core import api
from repro.core.bsr import rmat_edges

BS, W = 32, 48
n = 1024 * g
t = n // g
e = rmat_edges(12, seed=4)
e = e[(e[:, 0] < n) & (e[:, 1] < n)][:1000 * g]
rng = np.random.default_rng(5)
d = np.zeros((n, n), np.float32)
d[e[:, 0], e[:, 1]] = rng.random(len(e), dtype=np.float32) + 0.5
d[g * t - t:, :t] = 0.0                       # tile (g-1, 0): padding only
hub = (slice(BS, 2 * BS), slice(t + BS, t + 2 * BS))
d[hub] = rng.random((BS, BS), dtype=np.float32) + 0.5
d[BS, t + BS] = 0.0                           # a nearly dense hub block
d[1, t + 3] = 1.0
d[1, t + 4] = -0.0                            # beside an entry: stored
d[2, t + 2 * BS + 1] = np.nan
d[2 * BS, t + 3] = np.inf
b = rng.standard_normal((n, W)).astype(np.float32)
out = {}


def entries_gauge(plan):
    snap = obs.registry().snapshot()["plan.wire_entries"]
    return snap[f"algorithm=ring_c,stream=a,wire={plan.wire}"]


def tiles_held(tile_bytes):
    gc.collect()
    held, seen = collections.Counter(), set()
    for x in jax.live_arrays():
        if x.dtype != np.float32 or x.shape[-2:] != (BS, BS):
            continue
        for s in x.addressable_shards:
            key = (s.device.id, s.data.unsafe_buffer_pointer())
            if key not in seen:
                seen.add(key)
                held[s.device.id] += s.data.nbytes / tile_bytes
    return max(held.values())


a = api.DistBSR.from_dense(d, g=g, block_size=BS)
b_h = api.DistDense.for_rhs(b, a)
out["counts"] = a.entry_counts().tolist()
out["products"] = {}
for impl in ("interpret", "ref"):
    for overlap in ("off", "on"):
        if impl == "ref" and g > 2:
            continue
        got = {}
        for wire in ("auto", "padded"):
            plan = api.plan_matmul(a, b_h, algorithm="ring_c", impl=impl,
                                   wire=wire, overlap=overlap,
                                   validate="fast")
            c = np.asarray(plan(a, b_h))
            got[wire] = (plan.wire, plan._entry_cap, entries_gauge(plan), c)
        (we, cap, ge, ce), (wp, _, gp, cp) = got["auto"], got["padded"]
        finite = np.isfinite(ce).all(axis=1)
        want = d[finite].astype(np.float64) @ b
        out["products"][f"{impl}.{overlap}"] = {
            "wires": [we, wp], "cap": cap, "gauges": [ge, gp],
            "equal": bool((ce.view(np.uint32) == cp.view(np.uint32)).all()),
            "nonfinite_rows": int((~finite).sum()),
            "err": float(np.abs(ce[finite] - want).max())}
tile_bytes = a.tiled.store_capacity * BS * BS * 4
out["held"] = tiles_held(tile_bytes)

# a handle of the same blocks, every entry of them nonzero: same abstract
# shape, more entries a tile than the plan ships
plan = api.plan_matmul(a, b_h, algorithm="ring_c", impl="ref")
full = np.kron((np.abs(d).reshape(n // BS, BS, n // BS, BS)
                != 0).any(axis=(1, 3)), np.ones((BS, BS))).astype(np.float32)
a_full = api.DistBSR.from_dense(full, g=g, block_size=BS)
try:
    plan(a_full, api.DistDense.for_rhs(b, a_full))
    out["refused"] = ""
except ValueError as err:
    out["refused"] = str(err)
# block-dense: every entry nonzero
dense = rng.random((n, n), dtype=np.float32) + 0.5
a_d = api.DistBSR.from_dense(dense, g=g, block_size=BS)
plan = api.plan_matmul(a_d, api.DistDense.for_rhs(b, a_d),
                       algorithm="ring_c", impl="ref")
out["block_dense"] = [plan.wire, plan._entry_cap, entries_gauge(plan)]
# A's stored blocks each about a tenth full: shipping the entries would
# beat the block bytes eightfold, but their decode costs more than the
# exposed shift of the blocks
tenth = np.where(full != 0,
                 (rng.random((n, n)) < 0.1) * (rng.random((n, n)) + 0.5),
                 0).astype(np.float32)
a_t = api.DistBSR.from_dense(tenth, g=g, block_size=BS)
plan = api.plan_matmul(a_t, api.DistDense.for_rhs(b, a_t),
                       algorithm="ring_c", impl="ref")
out["tenth"] = [plan.wire, plan._entry_cap, entries_gauge(plan),
                float((a_t.entry_counts().max()) / (
                    a_t.tiled.store_capacity * BS * BS))]
# 512 x 512 blocks: too wide for the encoder's exact running counts, however
# sparse
wide = np.zeros((n, n), np.float32)
wide[np.arange(0, n, 97), np.arange(0, n, 97)[::-1]] = 1.0
a_w = api.DistBSR.from_dense(wide, g=g, block_size=512)
plan = api.plan_matmul(a_w, api.DistDense.for_rhs(b, a_w),
                       algorithm="ring_c", impl="ref")
out["wide_blocks"] = [plan.wire, plan._entry_cap, entries_gauge(plan)]
print(json.dumps(out))
"""


@pytest.fixture(scope="module", params=[2, 3], ids=["g2", "g3"])
def mesh_facts(request):
    g = request.param
    env = dict(os.environ, PYTHONPATH=str(pathlib.Path(__file__).resolve()
                                          .parents[1] / "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _MESH, str(g)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return g, json.loads(proc.stdout.strip().splitlines()[-1])


def test_entry_products_equal_padded_bit_for_bit(mesh_facts):
    g, facts = mesh_facts
    assert len(facts["products"]) == (2 if g > 2 else 4)
    cap = entries.entry_capacity(np.max(facts["counts"]))
    assert min(map(min, facts["counts"])) == 0      # the all-padding tile
    for key, p in facts["products"].items():
        assert p["wires"] == ["entries", "padded"], key
        assert p["cap"] == cap, key
        assert p["equal"], key
        # the NaN and the inf reach C's rows through A's entries
        assert p["nonfinite_rows"] >= 2, key
        assert p["err"] < 1e-4, key


def test_wire_entries_gauge_reads_what_the_stream_ships(mesh_facts):
    g, facts = mesh_facts
    cap = entries.entry_capacity(np.max(facts["counts"]))
    for key, p in facts["products"].items():
        assert p["gauges"] == [(g - 1) * cap, 0], key
    wire, ecap, gauge = facts["block_dense"]
    assert (wire, ecap, gauge) == ("padded", 0, 0)


def test_fuller_or_wider_blocks_keep_the_block_stream(mesh_facts):
    """An A whose stored blocks are each about a tenth full, and a sparse A
    of 512 x 512 blocks, plan on blocks under the default wire."""
    _, facts = mesh_facts
    wire, ecap, gauge, fill = facts["tenth"]
    assert (wire, ecap, gauge) == ("padded", 0, 0)
    assert 0.05 < fill < 0.1
    assert facts["wide_blocks"] == ["padded", 0, 0]


def test_entry_plan_refuses_a_handle_with_more_entries(mesh_facts):
    _, facts = mesh_facts
    assert "nonzero entries" in facts["refused"]


def test_entry_plans_hold_at_most_two_tiles_of_blocks(mesh_facts):
    _, facts = mesh_facts
    assert facts["held"] <= 2.0
