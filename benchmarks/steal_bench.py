"""Static work-stealing benchmark: skewed R-MAT SpMM on a 4x4 grid.

The acceptance experiment for the steal3d static dispatch: an unpermuted
R-MAT matrix (a=0.6 piles nonzeros into hub tiles) multiplied through every
owner-computes schedule — including the balanced-tiling variant of ring_c,
the strongest owner-computes contender — and through ``steal3d``, whose
plan executes the LPT equilibrium of the paper's SS3.4 locality-aware work
stealing.  The owner-computes rings execute ``g x store_capacity`` block
products per device (uniform padding: every device pays the hub tile's
capacity every step); steal3d executes its pair-list length — the stealing
equilibrium's makespan — so on skewed input it is measurably faster while
results stay allclose.  Also records the ``steal_simulation`` predictions,
the assignment statistics, the roofline moved-tile traffic split, and the
``algorithm="auto"`` choice under both the harness machine (compute-bound:
picks steal3d) and nominal v5e constants (net-bound: keeps a ring).

Runs in its own process (16 fake CPU devices must be configured before jax
imports).  Prints a single JSON object; ``benchmarks/run.py --json`` embeds
it in BENCH_kernels.json.

Usage:  python -m benchmarks.steal_bench [--scale 11] [--smoke]
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
    # Same geometry as balance_bench (scale-11 R-MAT, 256 dense columns,
    # bs=16): the per-step einsum sits well above the shard_map dispatch
    # floor of 16 fake CPU devices, so executed block products — the
    # quantity the stealing equilibrium shrinks — dominate the measurement.
    p.add_argument("--scale", type=int, default=11)
    p.add_argument("--n-cols", type=int, default=256)
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--repeats", type=int, default=5)
    p.add_argument("--smoke", action="store_true",
                   help="scale-8 quick pass")
    args = p.parse_args()
    if args.smoke:
        args.scale, args.repeats = 8, 1
        args.block_size, args.n_cols = 8, 64

    from repro.runtime.platform import set_host_device_count
    set_host_device_count(DEVICES)
    import jax.numpy as jnp  # noqa: E402  (after flag setup)
    import numpy as np

    from repro.core import api, roofline
    from repro.core.api import DistBSR, DistDense
    from repro.core.bsr import rmat_matrix
    from repro.core.dist import make_grid_mesh
    from repro.core.roofline import HOST_CPU, TPU_V5E
    from repro.core.schedule import steal_simulation

    g = 4
    a_dense = rmat_matrix(scale=args.scale, edgefactor=8, seed=0)
    b = np.random.default_rng(0).standard_normal(
        (a_dense.shape[1], args.n_cols)).astype(np.float32)
    mesh = make_grid_mesh(g)
    a_h = DistBSR.from_dense(a_dense, g=g, block_size=args.block_size)
    a_bal = DistBSR.from_dense(a_dense, g=g, block_size=args.block_size,
                               balance="rows")
    b_h = DistDense.for_rhs(jnp.asarray(b), a_h)
    b_bal = DistDense.for_rhs(jnp.asarray(b), a_bal)

    counts = np.asarray(a_h.counts, dtype=np.float64)
    out = {"rmat_scale": args.scale, "g": g,
           "block_size": args.block_size, "n_cols": args.n_cols,
           "a_capacity": a_h.capacity,
           "store_capacity": a_h.tiled.store_capacity,
           "steal_simulation": {
               "none": steal_simulation(counts, "none"),
               "random": steal_simulation(counts, "random",
                                          comm_penalty=1.0),
               "locality": steal_simulation(counts, "locality",
                                            comm_penalty=1.0),
           },
           "algorithms": {}}
    out["simulation_predicts_stealing_wins"] = \
        out["steal_simulation"]["locality"] < out["steal_simulation"]["none"]

    api.clear_plan_cache()
    # Phase 1: build + warm every plan (tracing/compilation happens here).
    plans, results = {}, {}
    contenders = [(alg, a_h, b_h) for alg in api.algorithms()]
    contenders.append(("ring_c[balanced]", a_bal, b_bal))
    for name, ah, bh in contenders:
        alg = name.split("[")[0]
        t0 = time.perf_counter()
        plan = api.plan_matmul(ah, bh, mesh=mesh, algorithm=alg,
                               impl="ref", cache=False)
        t_build = time.perf_counter() - t0
        t0 = time.perf_counter()
        c = plan(ah, bh)
        c.block_until_ready()
        t_first = time.perf_counter() - t0
        out["algorithms"][name] = {
            "plan_build_s": t_build,
            "first_call_s": t_first,
            "predicted_s_v5e": plan.predicted_cost(TPU_V5E),
            "predicted_s_host": plan.predicted_cost(HOST_CPU),
        }
        plans[name] = (plan, ah, bh)
        results[name] = np.asarray(c)

    # Phase 2: steady-state timing, schedules interleaved within each
    # repeat; min over repeats (subprocess scheduling noise on 16 fake
    # devices swamps a mean).
    times = {key: [] for key in plans}
    for _ in range(args.repeats):
        for key, (plan, ah, bh) in plans.items():
            times[key].append(
                _timed(lambda: plan(ah, bh).block_until_ready()))
    for key, ts in times.items():
        out["algorithms"][key]["per_multiply_s"] = min(ts)

    # Assignment + roofline detail for the steal3d plan.
    splan = plans["steal3d"][0].steal
    asg = splan.assignment
    cm = dict(splan.cost)
    out["steal3d"] = {
        "owner_makespan": asg.owner_makespan,
        "lpt_makespan": asg.makespan,
        "equilibrium_gain": asg.gain(),
        "n_moved_items": asg.n_moved,
        "pair_capacity": splan.pair_capacity,
        "owner_ring_block_products": g * a_h.tiled.store_capacity,
        "move_rounds": len(splan.a_deltas) + len(splan.b_deltas),
        "reduce_rounds": len(splan.row_deltas) + len(splan.col_deltas),
        "roofline_host": roofline.steal3d_model(
            cm["total_flops"], cm["gather_bytes"], cm["moved_tile_bytes"],
            cm["reduce_bytes"], cm["ai_local"], HOST_CPU),
    }

    out["allclose_steal3d_vs_ring_c"] = bool(np.allclose(
        results["steal3d"], results["ring_c"], atol=1e-4))
    owner_names = [n for n in out["algorithms"] if n != "steal3d"]
    best_owner = min(owner_names,
                     key=lambda n: out["algorithms"][n]["per_multiply_s"])
    t_owner = out["algorithms"][best_owner]["per_multiply_s"]
    t_steal = out["algorithms"]["steal3d"]["per_multiply_s"]
    out["best_owner_computes"] = best_owner
    out["steal3d_speedup_vs_best_owner"] = t_owner / t_steal \
        if t_steal else float("nan")

    # What the planner does on its own: compute-bound harness machine ->
    # steal3d; net-bound nominal v5e -> an owner-computes ring.
    choice_host, scores_host = api.auto_select(a_h, b_h, machine=HOST_CPU)
    choice_v5e, _ = api.auto_select(a_h, b_h, machine=TPU_V5E)
    out["auto"] = {"choice_host_cpu": choice_host,
                   "choice_tpu_v5e": choice_v5e,
                   "scores_host_cpu": scores_host}

    json.dump(out, sys.stdout, indent=1)
    print()
    return 0


if __name__ == "__main__":
    sys.exit(main())
