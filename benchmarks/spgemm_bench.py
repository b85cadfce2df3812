"""Symbolic/numeric SpGEMM benchmark: dense- vs sparse-output graph squaring.

The acceptance experiment for the sparse-output subsystem: an R-MAT
adjacency matrix squared (A @ A) and cubed (A @ A @ A) on a 4x4 grid, once
through the legacy dense-output SpGEMM path (every device materializes a
dense C tile; the result is a dense array that would need re-tiling to
multiply again) and once through ``output="sparse"`` (symbolic phase
predicts C's block structure, the numeric phase scatter-accumulates into
packed blocks, and the result is a ``DistBSR`` that chains directly into
the next multiply).  For hypersparse products the sparse path wins on both
output footprint and per-multiply time; both are recorded, along with the
symbolic-phase cost and the chained-cube timings.

Runs in its own process (16 fake CPU devices must be configured before jax
imports).  Prints a single JSON object; ``benchmarks/run.py --json`` embeds
it in BENCH_kernels.json.

Usage:  python -m benchmarks.spgemm_bench [--scale 12] [--smoke]
"""
from __future__ import annotations

import argparse
import json
import sys
import time

DEVICES = 16  # 4x4 grid


# obs.timed blocks on fn's result before reading the clock (async
# dispatch can't smear) — the check_api-sanctioned timing helper.
from repro.obs import timed as _timed  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    # scale-13 / edgefactor-1 / bs=8 keeps the product block-hypersparse
    # (predicted C density ~0.09, well under the output="auto" threshold) —
    # the graph-squaring regime the sparse path is for, and large enough
    # that the dense path's x(tile columns) cost factor dominates its
    # footprint advantage on this host harness too.  R-MAT's a=0.6
    # clustering fills blocks fast: at edgefactor 4 even A @ A is ~70%
    # block-dense and a dense output is the right call (which the bench's
    # "auto" record then shows).
    p.add_argument("--scale", type=int, default=13)
    p.add_argument("--edgefactor", type=int, default=1)
    p.add_argument("--block-size", type=int, default=8)
    p.add_argument("--repeats", type=int, default=3)
    p.add_argument("--smoke", action="store_true",
                   help="scale-9 quick pass")
    args = p.parse_args()
    if args.smoke:
        args.scale, args.repeats = 9, 2

    from repro.runtime.platform import set_host_device_count
    set_host_device_count(DEVICES)
    import numpy as np

    from repro.core import api
    from repro.core.api import DistBSR
    from repro.core.bsr import rmat_matrix
    from repro.core.dist import make_grid_mesh
    from repro.core.roofline import TPU_V5E

    g = 4
    a_dense = rmat_matrix(scale=args.scale, edgefactor=args.edgefactor,
                          seed=0)
    mesh = make_grid_mesh(g)
    a_h = DistBSR.from_dense(a_dense, g=g, block_size=args.block_size)
    m = a_dense.shape[0]

    out = {"rmat_scale": args.scale, "edgefactor": args.edgefactor, "g": g,
           "block_size": args.block_size,
           "a_capacity": a_h.capacity,
           "a_footprint_bytes": a_h.footprint_bytes(),
           "dense_bytes": int(m * m * 4),
           "output": {}}

    api.clear_plan_cache()
    t0 = time.perf_counter()
    sym = api.symbolic_spgemm(a_h.tiled, a_h.tiled)
    out["symbolic_phase_s"] = time.perf_counter() - t0
    out["predicted_c_density"] = sym.density()
    out["c_capacity"] = sym.capacity
    out["pair_capacity"] = sym.pair_capacity
    out["total_real_pairs"] = sym.total_real_pairs()

    results = {}
    plans = {}
    # Phase 1: build + warm both output modes (all tracing/compilation
    # happens here, before any steady-state timing).
    for output in ("dense", "sparse"):
        t0 = time.perf_counter()
        plan = api.plan_matmul(a_h, a_h, mesh=mesh, algorithm="ring_c",
                               impl="ref", output=output, cache=False)
        t_build = time.perf_counter() - t0
        t0 = time.perf_counter()
        c = plan(a_h, a_h)
        (c.tiled.blocks if output == "sparse" else c).block_until_ready()
        t_first = time.perf_counter() - t0
        if output == "sparse":
            results[output] = np.asarray(c.densify())
            out_bytes = c.footprint_bytes()
        else:
            results[output] = np.asarray(c)
            out_bytes = int(results[output].nbytes)
        out["output"][output] = {
            "plan_build_s": t_build,
            "first_call_s": t_first,
            "output_bytes": out_bytes,
            "predicted_s_v5e": plan.predicted_cost(TPU_V5E),
        }
        plans[output] = plan

    # Phase 2: steady-state per-multiply timing, modes interleaved within
    # each repeat; min over repeats (subprocess scheduling noise on 16
    # fake devices swamps a mean).
    times = {key: [] for key in plans}
    for _ in range(args.repeats):
        for output, plan in plans.items():
            if output == "sparse":
                fn = lambda: plan(a_h, a_h).tiled.blocks.block_until_ready()
            else:
                fn = lambda: plan(a_h, a_h).block_until_ready()
            times[output].append(_timed(fn))
    for output, ts in times.items():
        out["output"][output]["per_multiply_s"] = min(ts)

    out["allclose_dense_vs_sparse"] = bool(np.allclose(
        results["dense"], results["sparse"], atol=1e-2))
    d, s = out["output"]["dense"], out["output"]["sparse"]
    out["sparse_speedup"] = d["per_multiply_s"] / s["per_multiply_s"] \
        if s["per_multiply_s"] else float("nan")
    out["footprint_ratio"] = d["output_bytes"] / s["output_bytes"] \
        if s["output_bytes"] else float("nan")

    # Chained cube A @ A @ A: the sparse product handle is the next left
    # operand — no densify, no re-tile.
    c2 = plans["sparse"](a_h, a_h)
    plan3 = api.plan_matmul(c2, a_h, mesh=mesh, algorithm="ring_c",
                            impl="ref", output="sparse", cache=False)
    c3 = plan3(c2, a_h)
    c3.tiled.blocks.block_until_ready()
    t_chain = min(_timed(
        lambda: plan3(c2, a_h).tiled.blocks.block_until_ready())
        for _ in range(args.repeats))
    out["chain"] = {
        "c2_capacity": c2.capacity,
        "c3_capacity": c3.capacity,
        "c3_footprint_bytes": c3.footprint_bytes(),
        "per_multiply_s": t_chain,
        "predicted_c3_density": plan3.symbolic.density(),
    }

    # What the planner would do on its own.
    auto_plan = api.plan_matmul(a_h, a_h, mesh=mesh, algorithm="auto",
                                impl="ref", output="auto", cache=False)
    choice, scores = api.auto_select(a_h, a_h, machine=TPU_V5E,
                                     output="sparse")
    out["auto"] = {"output": auto_plan.output,
                   "algorithm": auto_plan.algorithm.name,
                   "sparse_choice": choice, "sparse_scores": scores}

    json.dump(out, sys.stdout, indent=1)
    print()
    return 0


if __name__ == "__main__":
    sys.exit(main())
