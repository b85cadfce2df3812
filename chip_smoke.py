#!/usr/bin/env python3
"""Smoke run of the distributed SpMM/SpGEMM engine on a TPU.

Drives the engine's main path through the entry points a user calls
(``DistBSR.from_dense`` -> ``plan_matmul`` -> the plan) at a real size:
one R-MAT power-law graph with the paper's parameters (a=0.6,
b=c=d=0.4/3), scale 15 (32,768 vertices), edge factor 16, block size 128
(the MXU tile), float32, made from ``--seed``.

    python chip_smoke.py             # one chip, g=1: SpMM A @ B (B 512 wide)
                                     # and sparse-output SpGEMM A @ A
    python chip_smoke.py --chips 4   # 2x2 mesh: ring_c, summa_ag and steal3d
                                     # SpMM, sparse-output SpGEMM via ring_c

Every plan runs the Pallas kernels (``impl="pallas"``).  Every result is
checked against an independent ``scipy.sparse`` product on the host: SpMM
within a stated float64 error bound, SpGEMM exactly.  On four chips the
handle's tiles (checked right after ``DistBSR.from_dense``, before any
plan), every plan's operands and its output must span all four devices.
Lines that start with ``info:`` are informational (set-up and steady
times, peak device memory, the kernel in the compiled HLO, errors); they
are not a benchmark.  The last line is one JSON object naming the
device.  Without a TPU the script exits non-zero before any phase and
prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SCALE = 15         # R-MAT scale: 2**15 vertices
EDGEFACTOR = 16
BLOCK = 128        # MXU tile
WIDTH = 512        # columns of the SpMM feature block
STEADY_CALLS = 5


def graph_phase(scale: int, edgefactor: int, seed: int, g: int,
                block_size: int = BLOCK):
    """R-MAT adjacency (0/1, float32) as an engine handle on a g x g grid
    and as an independent scipy CSR matrix.  Returns ``(a_h, csr,
    tiling_seconds)``."""
    import scipy.sparse as sps

    from repro.core.api import DistBSR
    from repro.core.bsr import rmat_edges

    n = 1 << scale
    edges = rmat_edges(scale, edgefactor, seed=seed)
    csr = sps.csr_matrix((np.ones(len(edges), np.float32),
                          (edges[:, 0], edges[:, 1])), shape=(n, n))
    csr.sum_duplicates()
    csr.data[:] = 1.0
    dense = np.zeros((n, n), np.float32)
    dense[edges[:, 0], edges[:, 1]] = 1.0
    t0 = time.perf_counter()
    a_h = DistBSR.from_dense(dense, g=g, block_size=block_size)
    a_h.tiled.blocks.block_until_ready()
    return a_h, csr, time.perf_counter() - t0


def _timed_calls(plan, a, b, block):
    """Set-up apart from steady state, on the host clock.

    ``lower_s`` places the operands on the plan's mesh and traces and
    lowers the plan; ``compile_s`` compiles it (short on a hit in the
    persistent compilation cache); ``first_call_s`` is the first multiply
    and ``steady_s`` the later ones, each timed to ``block_until_ready``.
    Returns ``(out, times, compiled_hlo_text)``.
    """
    t0 = time.perf_counter()
    lowered = plan.lower(a, b)
    t1 = time.perf_counter()
    hlo = lowered.compile().as_text()
    t2 = time.perf_counter()
    out = plan(a, b)
    block(out).block_until_ready()
    times = {"lower_s": t1 - t0, "compile_s": t2 - t1,
             "first_call_s": time.perf_counter() - t2, "steady_s": []}
    for _ in range(STEADY_CALLS):
        del out                       # one result on the device at a time
        t0 = time.perf_counter()
        out = plan(a, b)
        block(out).block_until_ready()
        times["steady_s"].append(time.perf_counter() - t0)
    return out, times, hlo


def _devices_of(tree) -> dict:
    return {k: sorted(d.id for d in v.devices()) for k, v in tree.items()}


def spmm_tolerance(csr, b: np.ndarray) -> np.ndarray:
    """Elementwise bound on |C - A @ B| for the SpMM kernel.

    The kernel's ``jnp.dot`` passes no precision, so Mosaic contracts at
    its default precision.  The bound assumes the weakest one TPUs use for
    float32 operands: one bf16 pass.  bf16 keeps 8 significant bits, so
    it rounds each operand with relative error at most 2**-8, or 2**-7
    where it truncates (A's 0/1 entries are exact); products accumulate in
    float32 (unit roundoff 2**-24 per addition, at most K + 1 additions
    per entry, K the longest row of A).  So
    ``|err_ij| <= (2**-7 + (K + 1) * 2**-24) * (|A| @ |B|)_ij``.
    """
    k = int(np.diff(csr.indptr).max()) if csr.nnz else 0
    u = 2.0 ** -7 + (k + 1) * 2.0 ** -24
    return u * (csr.astype(np.float64) @ np.abs(b.astype(np.float64)))


def spmm_phase(a_h, csr, *, seed: int, impl: str, mesh=None,
               algorithm: str = "ring_c", machine=None) -> dict:
    """C = A @ B through ``plan_matmul``, checked against scipy in float64."""
    import jax.numpy as jnp

    from repro.core.api import DistDense, plan_matmul

    b = np.random.default_rng(seed).standard_normal(
        (csr.shape[1], WIDTH)).astype(np.float32)
    b_h = DistDense.for_rhs(jnp.asarray(b), a_h)
    t0 = time.perf_counter()
    plan = plan_matmul(a_h, b_h, algorithm=algorithm, impl=impl, mesh=mesh,
                       machine=machine)
    t_plan = time.perf_counter() - t0
    out, times, hlo = _timed_calls(plan, a_h, b_h, lambda c: c)
    got = np.asarray(out, dtype=np.float64)
    want = csr.astype(np.float64) @ b.astype(np.float64)
    err = np.abs(got - want)
    bound = spmm_tolerance(csr, b)
    ratio = float((err / np.maximum(bound, np.finfo(np.float64).tiny))
                  .max())
    if got.shape != want.shape or not (err <= bound).all():
        raise RuntimeError(
            f"SpMM/{algorithm} exceeds its error bound: max |err| "
            f"{float(err.max())!r}, max err/bound {ratio!r}")
    return {"phase": "spmm", "algorithm": plan.algorithm.name, "impl": impl,
            "g": a_h.g, "plan_s": t_plan, **times,
            "max_abs_err": float(err.max()),
            "max_err_over_bound": ratio,
            "tpu_custom_call": "tpu_custom_call" in hlo,
            "a_devices": _devices_of(a_h.placed(plan.algorithm.a_placement)),
            "b_devices": _devices_of(b_h.placed(plan.algorithm.b_placement)),
            "out_devices": sorted(d.id for d in out.devices())}


def _blocks_by_position(c_h):
    """Sum of a sparse result's stored blocks per global block position
    (padding and coverage slots repeat positions and hold zeros)."""
    t = c_h.tiled
    g, bs = t.grid_shape[0], t.block_size
    nbr, nbc = t.tile_shape[0] // bs, t.tile_shape[1] // bs
    blocks = np.asarray(t.blocks).reshape(-1, bs, bs)
    i = np.arange(g)[:, None, None]
    j = np.arange(g)[None, :, None]
    key = ((i * nbr + np.asarray(t.rows)) * (g * nbc)
           + j * nbc + np.asarray(t.cols)).ravel()
    pos, first, inv = np.unique(key, return_index=True, return_inverse=True)
    acc = blocks[first]
    rest = np.setdiff1d(np.arange(len(key)), first)
    np.add.at(acc, inv[rest], blocks[rest])
    return pos, acc, g * nbc


def spgemm_phase(a_h, csr, *, impl: str, mesh=None,
                 algorithm: str = "ring_c", machine=None) -> dict:
    """Sparse-output C = A @ A through ``plan_matmul``, compared exactly
    with scipy: entries are 0/1 and every sum stays below 2**24, so
    float32 holds each product and sum exactly."""
    from repro.core.api import plan_matmul

    t0 = time.perf_counter()
    plan = plan_matmul(a_h, a_h, algorithm=algorithm, impl=impl, mesh=mesh,
                       output="sparse", machine=machine)
    t_plan = time.perf_counter() - t0      # includes the symbolic phase
    out, times, hlo = _timed_calls(plan, a_h, a_h, lambda c: c.tiled.blocks)
    bs = a_h.block_size
    ref = (csr @ csr).tobsr(blocksize=(bs, bs))
    ref.sort_indices()
    ref_rows = np.repeat(np.arange(ref.shape[0] // bs), np.diff(ref.indptr))
    pos, acc, nb = _blocks_by_position(out)
    ref_pos = ref_rows * nb + ref.indices
    at = np.searchsorted(pos, ref_pos)
    if not (at < len(pos)).all() or not (pos[np.minimum(at, len(pos) - 1)]
                                         == ref_pos).all():
        raise RuntimeError(f"SpGEMM/{algorithm}: the predicted structure "
                           "misses blocks of the reference product")
    extra = np.ones(len(pos), bool)
    extra[at] = False
    mismatched = int((acc[at] != ref.data).any(axis=(1, 2)).sum()
                     + acc[extra].any(axis=(1, 2)).sum())
    if mismatched:
        raise RuntimeError(f"SpGEMM/{algorithm}: {mismatched} blocks differ "
                           "from the scipy.sparse product")
    return {"phase": "spgemm", "algorithm": plan.algorithm.name,
            "impl": impl, "g": a_h.g, "wire": plan.wire,
            "plan_and_symbolic_s": t_plan, **times,
            "pairs": plan.symbolic.total_real_pairs(),
            "out_blocks": int(len(ref.data)), "max_abs_err": 0.0,
            "tpu_custom_call": "tpu_custom_call" in hlo,
            "a_devices": _devices_of(
                a_h.packed_wire(plan.algorithm.a_placement)
                if plan.wire == "packed"
                else a_h.placed(plan.algorithm.a_placement)),
            "out_devices": sorted(d.id for d in out.tiled.blocks.devices())}


def _peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    return repr(stats.get("peak_bytes_in_use", "not reported"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform!r} devices", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} TPU chips, JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    from repro.core import roofline
    from repro.core.dist import make_grid_mesh
    from repro.runtime.platform import enable_compile_cache

    cache_dir = enable_compile_cache()
    machine = roofline.machine_for(dev.device_kind)
    print(f"info: device platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(devices)} jax={jax.__version__} "
          f"machine={machine.name} compile_cache={cache_dir}", flush=True)

    g = 2 if args.chips == 4 else 1
    mesh = make_grid_mesh(g)
    t0 = time.perf_counter()
    a_h, csr, t_tile = graph_phase(SCALE, EDGEFACTOR, args.seed, g)
    print(f"info: graph rmat scale={SCALE} edgefactor={EDGEFACTOR}"
          f" seed={args.seed} n={csr.shape[0]} nnz={csr.nnz} "
          f"block={BLOCK} g={g} stored_blocks={int(np.asarray(a_h.counts).sum())}"
          f" capacity={a_h.capacity} generate_s={time.perf_counter() - t0!r}"
          f" tiling_s={t_tile!r}", flush=True)
    # The handle itself, before any plan: tile (i, j) on device (i, j)
    # from the start, so staging on one device cannot come back unseen.
    mesh_ids = sorted(d.id for d in mesh.devices.flat)
    handle_devices = _devices_of({k: getattr(a_h.tiled, k)
                                  for k in ("blocks", "rows", "cols")})
    print("info: " + json.dumps({"phase": "handle",
                                 "devices": handle_devices}), flush=True)
    if any(ids != mesh_ids for ids in handle_devices.values()):
        raise RuntimeError(f"handle: DistBSR.from_dense's tiles not spread "
                           f"over the mesh {mesh_ids}: {handle_devices}")

    runs = []
    spmm_algs = ("ring_c", "summa_ag", "steal3d") if g > 1 else ("ring_c",)
    for alg in spmm_algs:
        runs.append(spmm_phase(a_h, csr, seed=args.seed, impl="pallas",
                               mesh=mesh, algorithm=alg, machine=machine))
        runs[-1]["peak_bytes_in_use"] = _peak_bytes(dev)
        print("info: " + json.dumps(runs[-1]), flush=True)
    runs.append(spgemm_phase(a_h, csr, impl="pallas", mesh=mesh,
                             algorithm="ring_c", machine=machine))
    runs[-1]["peak_bytes_in_use"] = _peak_bytes(dev)
    print("info: " + json.dumps(runs[-1]), flush=True)
    for r in runs:
        if not r["tpu_custom_call"]:
            raise RuntimeError(f"{r['phase']}/{r['algorithm']}: no Pallas "
                               "kernel (tpu_custom_call) in the compiled HLO")
        placed = [r["out_devices"]] + [ids for key in ("a_devices",
                                                       "b_devices")
                                       for ids in r.get(key, {}).values()]
        if any(ids != mesh_ids for ids in placed):
            raise RuntimeError(f"{r['phase']}/{r['algorithm']}: operands or "
                               f"output not spread over the mesh {mesh_ids}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
