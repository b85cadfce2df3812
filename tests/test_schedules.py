"""The schedule layer's drivers (``repro.core.schedules``), run directly.

On g x g virtual CPU devices (a process of its own for each g):

* the ring driver, over g in {1, 2, 3}, both ``overlap`` forms and three
  stream sets (one stream, A+B, ``ring_c_bidir``'s four at +-1): its
  ``consume`` sees tile generations 0 .. g-1 in order, each with its own
  ``xs[t]``, and the traced program shifts every stream g - 1 times;
* the broadcast driver at g = 2, both forms: step k's panels come from
  grid column / row k, in order, each with ``xs[k]``;
* the ring plans (``ring_c``, ``ring_a``, ``ring_c_bidir`` on padded and
  packed wires, and sparse-output ``ring_c``) at g = 2 and 3: the
  ppermute permutations in the traced program are exactly the ones the
  ``schedule.ppermute-bijection`` rule verifies.
"""
import functools
import json
import os
import pathlib
import subprocess
import sys

import pytest

_FACTS = r"""
import json, sys
import numpy as np
from repro.runtime.platform import set_host_device_count
g = int(sys.argv[1])
set_host_device_count(g * g)
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.analysis import schedule_check
from repro.analysis.jaxpr_lint import subjaxprs, trace_plan
from repro.core import api, schedules
from repro.core.bsr import random_sparse
from repro.core.dist import make_grid_mesh

mesh = make_grid_mesh(g)
STREAMS = {"one": (("col", 1),),
           "ab": (("col", 1), ("row", 1)),
           "bidir": (("col", 1), ("col", -1), ("row", 1), ("row", -1))}


def geom(overlap):
    return schedules._Geom(g=g, tm=1, tn=1, a_nbr=0, b_nbr=0, b_nbc=0,
                           impl="ref", axr="row", axc="col",
                           out_dtype=jnp.float32, overlap=overlap)


def weighted_ppermutes(jaxpr, mult=1, out=None):
    # ppermute executions by operand size (stream s rides s + 1 floats)
    out = {} if out is None else out
    for eqn in jaxpr.eqns:
        m = mult * int(eqn.params["length"]) \
            if eqn.primitive.name == "scan" else mult
        if eqn.primitive.name == "ppermute":
            size = int(eqn.invars[0].aval.size)
            out[size] = out.get(size, 0) + m
        for v in eqn.params.values():
            for sub in subjaxprs(v):
                weighted_ppermutes(sub, m, out)
    return out


def perms_in(jaxpr, out=None):
    out = set() if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "ppermute":
            out.add(tuple(map(tuple, eqn.params["perm"])))
        for v in eqn.params.values():
            for sub in subjaxprs(v):
                perms_in(sub, out)
    return out


def run(body):
    # body(my_id) on each device, my_id = 100 * row + col
    ids = jnp.asarray(100 * np.arange(g)[:, None] + np.arange(g)[None, :],
                      jnp.float32)
    fn = jax.jit(jax.shard_map(
        lambda x: body(x[0, 0])[None, None], mesh=mesh,
        in_specs=P("row", "col"), out_specs=P("row", "col"),
        check_vma=False))
    return np.asarray(fn(ids)), jax.make_jaxpr(fn)(ids).jaxpr


def logger(gm, n):
    # consume: row t of the log holds what the t-th call saw
    def consume(c, tiles, x):
        count, log = c
        row = jnp.stack([t[0] for t in tiles] + [x["t"]])
        return count + 1, log.at[count].set(row)
    c0 = (jnp.int32(0), schedules._pvary(jnp.zeros((g, n + 1)), gm))
    return consume, c0


out = {"ring": {}, "bcast": {}, "perms": {}}
for overlap in (False, True):
    gm = geom(overlap)
    for name, streams in STREAMS.items():
        def body(me, streams=streams, gm=gm):
            consume, c0 = logger(gm, len(streams))
            payloads = [jnp.full((s + 1,), me + 1000 * s)
                        for s in range(len(streams))]
            xs = {"t": 10.0 * jnp.arange(g) + 7}
            count, log = schedules.ring(
                [(p, ax, sign) for p, (ax, sign) in zip(payloads, streams)],
                consume, c0, xs, gm)
            return jnp.concatenate([log.reshape(-1), count[None] * 1.0])
        got, jaxpr = run(body)
        out["ring"][f"{name}.{overlap}"] = {
            "log": got.reshape(g, g, -1).tolist(),
            "ppermutes": weighted_ppermutes(jaxpr)}
    if g == 2:
        def body(me, gm=gm):
            consume, c0 = logger(gm, 2)
            a, b = jnp.full((1,), me), jnp.full((2,), me)
            count, log = schedules.bcast_steps(
                schedules._summa_bcast(a, b, gm), consume, c0,
                {"t": 10.0 * jnp.arange(g) + 7}, gm)
            return jnp.concatenate([log.reshape(-1), count[None] * 1.0])
        got, _ = run(body)
        out["bcast"][str(overlap)] = got.reshape(g, g, -1).tolist()

if g > 1:
    a_h = api.DistBSR.from_dense(random_sparse(32 * g, 32 * g, 0.2, seed=0),
                                 g=g, block_size=4)
    b_h = api.DistBSR.from_dense(random_sparse(32 * g, 32 * g, 0.05,
                                               seed=1), g=g, block_size=4)
    cases = [(alg, wire, "dense")
             for alg in ("ring_c", "ring_a", "ring_c_bidir")
             for wire in ("padded", "packed")] + [("ring_c", "auto",
                                                   "sparse")]
    for alg, wire, output in cases:
        plan = api.plan_matmul(a_h, b_h, mesh=mesh, algorithm=alg,
                               impl="ref", wire=wire, output=output,
                               cache=False)
        checked = {tuple(map(tuple, p))
                   for _, p in schedule_check.plan_perms(plan)}
        traced = perms_in(trace_plan(plan, a_h, b_h))
        out["perms"][f"{alg}.{wire}.{output}"] = {
            "wire": plan.wire, "checked": sorted(checked),
            "traced": sorted(traced)}
print(json.dumps(out))
"""


@functools.lru_cache(maxsize=None)
def _facts(g: int) -> dict:
    env = dict(os.environ, PYTHONPATH=str(pathlib.Path(__file__).resolve()
                                          .parents[1] / "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _FACTS, str(g)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


_STREAMS = {"one": (("col", 1),),
            "ab": (("col", 1), ("row", 1)),
            "bidir": (("col", 1), ("col", -1), ("row", 1), ("row", -1))}
_RING_CASES = [(g, ov, name) for g in (1, 2, 3) for ov in (False, True)
               for name in _STREAMS]
_RING_IDS = [f"g{g}-{'overlap' if ov else 'bulk'}-{name}"
             for g, ov, name in _RING_CASES]


@pytest.mark.parametrize("g,overlap,streams", _RING_CASES, ids=_RING_IDS)
def test_ring_driver_consumes_each_generation_with_its_inputs(g, overlap,
                                                              streams):
    """consume's t-th call gets every stream's tile after t shifts (device
    (i, j) holds the tile of the device ``t * direction`` further along
    the stream's axis) beside ``xs[t]``: generations 0 .. g-1, in order."""
    fact = _facts(g)["ring"][f"{streams}.{overlap}"]
    dirs = _STREAMS[streams]
    for i in range(g):
        for j in range(g):
            cell = fact["log"][i][j]
            log, count = cell[:-1], cell[-1]
            assert count == g
            rows = [log[t * (len(dirs) + 1):(t + 1) * (len(dirs) + 1)]
                    for t in range(g)]
            for t, row in enumerate(rows):
                want = []
                for s, (axis, sign) in enumerate(dirs):
                    src = ((i + sign * t) % g, j) if axis == "row" \
                        else (i, (j + sign * t) % g)
                    want.append(100 * src[0] + src[1] + 1000 * s)
                assert row == want + [10 * t + 7], (i, j, t)


@pytest.mark.parametrize("g,overlap,streams", _RING_CASES, ids=_RING_IDS)
def test_ring_driver_shifts_every_stream_g_minus_1_times(g, overlap,
                                                         streams):
    """Each stream's ppermutes in the traced program, counted once per
    scanned step, are g - 1: no transfer that no step consumes (none at
    all at g = 1)."""
    fact = _facts(g)["ring"][f"{streams}.{overlap}"]
    want = {str(s + 1): g - 1 for s in range(len(_STREAMS[streams]))}
    got = {k: v for k, v in fact["ppermutes"].items() if v}
    assert got == {k: v for k, v in want.items() if v}


@pytest.mark.parametrize("overlap", [False, True], ids=["bulk", "overlap"])
def test_bcast_driver_consumes_step_k_panels_in_order(overlap):
    """summa_bcast's driver at g = 2: step k consumes A's tile of grid
    column k and B's of grid row k, with ``xs[k]``, for k = 0, 1."""
    g = 2
    log = _facts(g)["bcast"][str(overlap)]
    for i in range(g):
        for j in range(g):
            cell = log[i][j]
            assert cell[-1] == g
            for k in range(g):
                assert cell[3 * k:3 * k + 3] == [100 * i + k, 100 * k + j,
                                                 10 * k + 7], (i, j, k)


_PERM_CASES = [(g, alg, wire, output) for g in (2, 3)
               for alg, wire, output in
               [(alg, wire, "dense")
                for alg in ("ring_c", "ring_a", "ring_c_bidir")
                for wire in ("padded", "packed")]
               + [("ring_c", "auto", "sparse")]]


@pytest.mark.parametrize(
    "g,alg,wire,output", _PERM_CASES,
    ids=[f"g{g}-{a}-{w}-{o}" for g, a, w, o in _PERM_CASES])
def test_traced_ring_permutations_are_the_checked_ones(g, alg, wire,
                                                       output):
    """The permutations a ring plan's program hands to ppermute are
    exactly those ``schedule.ppermute-bijection`` verifies for it."""
    fact = _facts(g)["perms"][f"{alg}.{wire}.{output}"]
    assert fact["wire"] == ("packed" if wire == "auto" else wire)
    assert fact["traced"] == fact["checked"]
