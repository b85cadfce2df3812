"""Packed wire format benchmark: padded vs packed shipments on skewed R-MAT.

The acceptance experiment for the packed communication layer
(``plan_matmul(wire="packed")``, ``repro.core.wire``): an unpermuted
skewed R-MAT SpMM on a 4x4 grid multiplied through ``ring_c``,
``summa_ag`` and ``steal3d`` with both wire layouts.  Padded plans ship
every sparse A tile at the uniform ``store_capacity`` stride (hub-tile
capacity + coverage blocks + rows/cols index arrays); packed plans ship
only real blocks at the bucketed wire capacity, with steal3d's
moved-tile rounds additionally sliced to their per-move real max and its
partial-C reductions row-packed.  Records ``wire_bytes_padded`` vs
``wire_bytes_packed`` per algorithm (the cost-model byte terms the
auto-scheduler ranks on), measured per-multiply times for both, and an
``auto_select`` comparison under both scorings; also one sparse-output
SpGEMM record (A @ A via ``ring_c``), where packing drops the coverage
blocks from both operands' block streams.

The run *asserts* the packed contract — packed bytes <= padded for every
algorithm and packed results allclose to padded — and exits non-zero on
violation, so the ``--smoke`` tier-1 path enforces it in CI.

Runs in its own process (16 fake CPU devices must be configured before
jax imports).  Prints a single JSON object; ``benchmarks/run.py --json``
embeds it in BENCH_kernels.json.

Usage:  python -m benchmarks.wire_bench [--scale 11] [--smoke]
"""
from __future__ import annotations

import argparse
import json
import sys
import time

DEVICES = 16  # 4x4 grid

ALGORITHMS = ("ring_c", "summa_ag", "steal3d")


# obs.timed blocks on fn's result before reading the clock (async
# dispatch can't smear) — the check_api-sanctioned timing helper.
from repro.obs import timed as _timed  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    # Same geometry as steal_bench/balance_bench: scale-11 R-MAT, 256
    # dense columns, bs=16 — skewed enough that the hub tile's capacity
    # (what the padded wire pays everywhere) is a large multiple of the
    # typical tile's real block count.
    p.add_argument("--scale", type=int, default=11)
    p.add_argument("--n-cols", type=int, default=256)
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--repeats", type=int, default=5)
    p.add_argument("--smoke", action="store_true",
                   help="scale-8 quick pass")
    args = p.parse_args()
    if args.smoke:
        args.scale, args.repeats = 8, 2
        args.block_size, args.n_cols = 8, 64

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
    a_dense = rmat_matrix(scale=args.scale, edgefactor=8, seed=0)
    b = np.random.default_rng(0).standard_normal(
        (a_dense.shape[1], args.n_cols)).astype(np.float32)
    mesh = make_grid_mesh(g)
    a_h = DistBSR.from_dense(a_dense, g=g, block_size=args.block_size)
    b_h = DistDense.for_rhs(jnp.asarray(b), a_h)

    out = {"rmat_scale": args.scale, "g": g,
           "block_size": args.block_size, "n_cols": args.n_cols,
           "a_capacity": a_h.capacity,
           "a_store_capacity": a_h.tiled.store_capacity,
           "a_wire_capacity": a_h.packed_operand().wire_capacity,
           "algorithms": {}}

    api.clear_plan_cache()
    failures = []
    plans = {}
    # Phase 1: build + warm every (algorithm, wire) plan.
    for alg in ALGORITHMS:
        for wire in ("padded", "packed"):
            t0 = time.perf_counter()
            plan = api.plan_matmul(a_h, b_h, mesh=mesh, algorithm=alg,
                                   impl="ref", wire=wire, cache=False)
            t_build = time.perf_counter() - t0
            c = plan(a_h, b_h)
            c.block_until_ready()
            plans[alg, wire] = (plan, np.asarray(c), t_build)

    # Phase 2: steady-state timing, variants interleaved per repeat.
    times = {key: [] for key in plans}
    for _ in range(args.repeats):
        for key, (plan, _c, _t) in plans.items():
            times[key].append(
                _timed(lambda p=plan: p(a_h, b_h).block_until_ready()))

    for alg in ALGORITHMS:
        plan_d, c_d, tb_d = plans[alg, "padded"]
        plan_p, c_p, tb_p = plans[alg, "packed"]
        cm_d, cm_p = plan_d.cost_model(), plan_p.cost_model()
        allclose = bool(np.allclose(c_p, c_d, atol=1e-4))
        rec = {
            "wire_bytes_padded": cm_d["total_net_bytes"],
            "wire_bytes_packed": cm_p["total_net_bytes"],
            "wire_reduction": cm_d["total_net_bytes"]
            / cm_p["total_net_bytes"]
            if cm_p["total_net_bytes"] else float("inf"),
            "plan_build_s_padded": tb_d,
            "plan_build_s_packed": tb_p,
            "per_multiply_s_padded": min(times[alg, "padded"]),
            "per_multiply_s_packed": min(times[alg, "packed"]),
            "predicted_s_v5e_padded": plan_d.predicted_cost(TPU_V5E),
            "predicted_s_v5e_packed": plan_p.predicted_cost(TPU_V5E),
            "allclose_packed_vs_padded": allclose,
        }
        if alg == "steal3d":
            rec["moved_tile_bytes_padded"] = \
                plan_d.steal.cost["moved_tile_bytes"]
            rec["moved_tile_bytes_packed"] = \
                plan_p.steal.cost["moved_tile_bytes"]
            rec["reduce_bytes_padded"] = plan_d.steal.cost["reduce_bytes"]
            rec["reduce_bytes_packed"] = plan_p.steal.cost["reduce_bytes"]
        out["algorithms"][alg] = rec
        if not allclose:
            failures.append(f"{alg}: packed result diverges from padded")
        if cm_p["total_net_bytes"] > cm_d["total_net_bytes"]:
            failures.append(
                f"{alg}: packed wire bytes {cm_p['total_net_bytes']:.0f} "
                f"> padded {cm_d['total_net_bytes']:.0f}")

    # sparse-output SpGEMM pair traffic: A @ A through the packed wire
    c_pack = api.plan_matmul(a_h, a_h, mesh=mesh, algorithm="ring_c",
                             impl="ref", output="sparse", cache=False)
    c_pad = api.plan_matmul(a_h, a_h, mesh=mesh, algorithm="ring_c",
                            impl="ref", output="sparse", wire="padded",
                            cache=False)
    r_pack, r_pad = c_pack(a_h, a_h), c_pad(a_h, a_h)
    sp_close = bool(np.allclose(np.asarray(r_pack.densify()),
                                np.asarray(r_pad.densify()), atol=1e-3))
    out["spgemm_sparse_output"] = {
        "auto_wire": c_pack.wire,
        "wire_bytes_padded": c_pad.cost_model()["total_net_bytes"],
        "wire_bytes_packed": c_pack.cost_model()["total_net_bytes"],
        "allclose_packed_vs_padded": sp_close,
    }
    if c_pack.wire != "packed" or not sp_close:
        failures.append("sparse-output packed wire check failed")
    if out["spgemm_sparse_output"]["wire_bytes_packed"] > \
            out["spgemm_sparse_output"]["wire_bytes_padded"]:
        failures.append("sparse-output packed bytes exceed padded")

    # what the auto-scheduler does under each scoring
    choice_pad, _ = api.auto_select(a_h, b_h, wire="padded")
    choice_pack, scores_pack = api.auto_select(a_h, b_h, wire="packed")
    out["auto"] = {"choice_v5e_padded": choice_pad,
                   "choice_v5e_packed": choice_pack,
                   "scores_v5e_packed": scores_pack}

    out["packed_never_wider"] = not any("wire bytes" in f
                                        for f in failures)
    json.dump(out, sys.stdout, indent=1)
    print()
    if failures:
        print("wire_bench FAILED: " + "; ".join(failures), file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
