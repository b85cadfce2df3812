"""Balanced-tiling benchmark: R-MAT scale-11 SpMM on a 4x4 grid.

The acceptance experiment for sparsity-aware capacity planning: an
unpermuted R-MAT matrix (a=0.6 piles nonzeros into low row blocks) tiled
with ``balance="none"`` vs ``balance="rows"``.  Balancing spreads nonzero
blocks across grid rows, shrinking the uniform tile capacity — i.e. the
block products every device *executes* per ring step — so the balanced
plan is measurably faster, while the carried row permutation is inverted
in the epilogue and results stay allclose.

Runs in its own process (16 fake CPU devices must be configured before jax
imports).  Prints a single JSON object; ``benchmarks/run.py --json`` embeds
it in BENCH_kernels.json.

Usage:  python -m benchmarks.balance_bench [--scale 11] [--smoke]
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
    # scale-11 / 256 dense columns keeps the per-step einsum well above the
    # ~30ms shard_map dispatch floor of 16 fake CPU devices, so the
    # capacity reduction (the real flop saving) dominates the measurement;
    # bs=16 keeps 32x32 block slots per tile — enough block-level
    # granularity for row balancing to bite (bs=32 leaves few slots and the
    # hub tile saturates either way).
    p.add_argument("--scale", type=int, default=11)
    p.add_argument("--n-cols", type=int, default=256)
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--repeats", type=int, default=5)
    p.add_argument("--smoke", action="store_true",
                   help="scale-8 quick pass")
    args = p.parse_args()
    if args.smoke:
        args.scale, args.repeats = 8, 1
        args.block_size, args.n_cols = 16, 32

    from repro.runtime.platform import set_host_device_count
    set_host_device_count(DEVICES)
    import jax.numpy as jnp  # noqa: E402  (after flag setup)
    import numpy as np

    from repro.core import api
    from repro.core.api import DistBSR, DistDense
    from repro.core.bsr import rmat_matrix
    from repro.core.dist import make_grid_mesh
    from repro.core.roofline import TPU_V5E

    g = 4
    # No vertex relabeling: keep the R-MAT hub skew that makes uniform
    # capacity worst (the load-balancing target).
    a_dense = rmat_matrix(scale=args.scale, edgefactor=8, seed=0)
    b = np.random.default_rng(0).standard_normal(
        (a_dense.shape[1], args.n_cols)).astype(np.float32)
    mesh = make_grid_mesh(g)

    out = {"rmat_scale": args.scale, "g": g, "block_size": args.block_size,
           "n_cols": args.n_cols, "balance": {}}
    results = {}
    plans = {}
    # Phase 1: build + warm every (balance, algorithm) plan.  All tracing,
    # compilation and buffer churn happens here, before any timing.
    for balance in ("none", "rows"):
        t0 = time.perf_counter()
        a_h = DistBSR.from_dense(a_dense, g=g, block_size=args.block_size,
                                 balance=balance)
        t_tile = time.perf_counter() - t0
        b_h = DistDense.for_rhs(jnp.asarray(b), a_h)
        entry = {
            "tiling_s": t_tile,
            "capacity": a_h.capacity,
            "store_capacity": a_h.tiled.store_capacity,
            "padded_flop_waste": a_h.tiled.padded_flop_waste(),
            "load_imbalance": a_h.tiled.load_imbalance(),
            "algorithms": {},
        }
        for alg in api.algorithms():
            t0 = time.perf_counter()
            plan = api.plan_matmul(a_h, b_h, mesh=mesh, algorithm=alg,
                                   impl="ref", cache=False)
            t_build = time.perf_counter() - t0
            t0 = time.perf_counter()
            c = plan(a_h, b_h)
            c.block_until_ready()
            t_first = time.perf_counter() - t0
            entry["algorithms"][alg] = {
                "plan_build_s": t_build,
                "first_call_s": t_first,
                "predicted_s_v5e": plan.predicted_cost(TPU_V5E),
            }
            plans[(balance, alg)] = (plan, a_h, b_h)
            if alg == "ring_c":
                results[balance] = np.asarray(c)
        choice, scores = api.auto_select(a_h, b_h, machine=TPU_V5E)
        entry["auto_choice"] = choice
        entry["auto_scores"] = scores
        out["balance"][balance] = entry
    # Phase 2: steady-state timing, balanced/unbalanced interleaved within
    # each repeat so machine drift hits both equally; min over repeats
    # (host-process scheduling noise on 16 fake CPU devices swamps a mean).
    times = {key: [] for key in plans}
    for _ in range(args.repeats):
        for key, (plan, a_h, b_h) in plans.items():
            times[key].append(
                _timed(lambda: plan(a_h, b_h).block_until_ready()))
    for (balance, alg), ts in times.items():
        out["balance"][balance]["algorithms"][alg]["per_multiply_s"] = min(ts)

    out["allclose_balanced_vs_none"] = bool(np.allclose(
        results["none"], results["rows"], atol=1e-4))
    none, rows = out["balance"]["none"], out["balance"]["rows"]
    out["waste_reduction"] = (none["padded_flop_waste"]
                              - rows["padded_flop_waste"])
    t_n = none["algorithms"]["ring_c"]["per_multiply_s"]
    t_r = rows["algorithms"]["ring_c"]["per_multiply_s"]
    out["ring_c_speedup_balanced"] = t_n / t_r if t_r else float("nan")
    json.dump(out, sys.stdout, indent=1)
    print()
    return 0


if __name__ == "__main__":
    sys.exit(main())
