"""Elastic recovery benchmark: device loss on a 3x3 grid, shrink to 2x2.

Measures the headline number of the elastic replanning runtime: **time to
recover** — from "5 of 9 devices are gone" to the first correct product
on the surviving 2x2 grid (device-side reshard of the operands, a rebuilt
locality-aware steal3d assignment validated by the static checker, plan
build, first multiply) — against the **cold rebuild** alternative (host
round-trip: densify, re-tile from scratch at g=2, plan, first multiply).
Also reports the post-recovery per-multiply time next to the cold-built
plan's, since a recovery that leaves a slow plan behind would be a
pyrrhic one.

Asserts (exit non-zero on violation): the recovered product matches the
dense reference, recovery touches no host round-trip yet lands within a
generous slack of the cold rebuild, and the ``replan.*`` counters are in
the obs registry.

Runs in its own process (9 fake CPU devices must be configured before jax
imports).  Prints a single JSON object; ``benchmarks/run.py --json``
embeds it in BENCH_kernels.json under ``elastic``.

Usage:  python -m benchmarks.elastic_bench [--scale 9] [--smoke]
"""
from __future__ import annotations

import argparse
import json
import sys

DEVICES = 9  # 3x3 grid before the loss

# obs.timed blocks on fn's result before reading the clock (async
# dispatch can't smear) — the check_api-sanctioned timing helper.
from repro.obs import timed as _timed  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--scale", type=int, default=9)
    p.add_argument("--n-cols", type=int, default=64)
    p.add_argument("--block-size", type=int, default=8)
    p.add_argument("--repeats", type=int, default=5)
    p.add_argument("--smoke", action="store_true",
                   help="scale-6 quick pass")
    args = p.parse_args()
    if args.smoke:
        args.scale, args.repeats = 6, 2
        args.n_cols = 48

    from repro.runtime.platform import set_host_device_count
    set_host_device_count(DEVICES)
    import jax.numpy as jnp  # noqa: E402  (after flag setup)
    import numpy as np

    from repro import obs
    from repro.core import api
    from repro.core.api import DistBSR, DistDense
    from repro.core.bsr import rmat_matrix
    from repro.core.dist import make_grid_mesh
    from repro.runtime.faultinject import DeviceLoss
    from repro.runtime.replan import ElasticReplanner

    obs.reset_all()
    obs.enable(clear=True)
    a_dense = rmat_matrix(scale=args.scale, edgefactor=8, seed=0)
    b = np.random.default_rng(0).standard_normal(
        (a_dense.shape[1], args.n_cols)).astype(np.float32)
    want = a_dense @ b

    # pre-loss steady state: steal3d on the full 3x3 grid
    mesh3 = make_grid_mesh(3)
    a3 = DistBSR.from_dense(a_dense, g=3, block_size=args.block_size)
    b3 = DistDense.for_rhs(jnp.asarray(b), a3)
    p3 = api.plan_matmul(a3, b3, algorithm="steal3d", mesh=mesh3,
                         validate="fast")
    pre_multiply_s = _timed(lambda: p3(a3, b3), repeats=args.repeats,
                            warmup=1)

    loss = DeviceLoss(DEVICES, 5, seed=0)
    mesh2 = make_grid_mesh(2)

    # cold rebuild first (any process-wide compile caching then favors
    # neither side — the recovery path runs second and is the one we
    # bound): host round-trip + re-tile + fresh plan + first multiply
    def cold():
        a2 = DistBSR.from_dense(a_dense, g=2, block_size=args.block_size)
        b2 = DistDense.for_rhs(jnp.asarray(b), a2)
        plan = api.plan_matmul(a2, b2, algorithm="steal3d", mesh=mesh2,
                               validate="fast", cache=False)
        return plan, a2, b2

    cold_plan, a2c, b2c = [None] * 3

    def cold_to_first_result():
        nonlocal cold_plan, a2c, b2c
        cold_plan, a2c, b2c = cold()
        return cold_plan(a2c, b2c)

    cold_rebuild_s = _timed(cold_to_first_result)
    cold_multiply_s = _timed(lambda: cold_plan(a2c, b2c),
                             repeats=args.repeats, warmup=1)

    # elastic recovery: device-side reshard + rebuilt steal3d assignment
    # (validated against the survivor set) + plan + first multiply
    rp = ElasticReplanner()
    rec = None

    def recover_to_first_result():
        nonlocal rec
        rec = rp.recover_from_loss(a3, b3, loss.survivors(), mesh=mesh2)
        return rec.plan(rec.a, rec.b)

    time_to_recover_s = _timed(recover_to_first_result)
    post_multiply_s = _timed(lambda: rec.plan(rec.a, rec.b),
                             repeats=args.repeats, warmup=1)

    got = np.asarray(rec.plan(rec.a, rec.b))
    err = float(np.max(np.abs(got[:want.shape[0], :want.shape[1]] - want)))
    snap = obs.registry().snapshot()
    replan_metrics = {k: v for k, v in snap.items()
                      if k.startswith("replan.")}

    out = {
        "smoke": bool(args.smoke),
        "rmat_scale": args.scale,
        "n_cols": args.n_cols,
        "block_size": args.block_size,
        "g_before": 3,
        "g_after": rec.g,
        "survivors": list(loss.survivors()),
        "pre_multiply_s": pre_multiply_s,
        "time_to_recover_s": time_to_recover_s,
        "cold_rebuild_s": cold_rebuild_s,
        "recover_over_cold": time_to_recover_s / cold_rebuild_s,
        "post_multiply_s": post_multiply_s,
        "cold_multiply_s": cold_multiply_s,
        "plans_evicted": rec.evicted,
        "max_err_recovered": err,
        "replan_metrics": replan_metrics,
    }
    print(json.dumps(out, indent=1))

    ok = True
    if err > 1e-3:
        print(f"FAIL: recovered product off by {err:.3e}", file=sys.stderr)
        ok = False
    if not (0.0 < time_to_recover_s < float("inf")):
        print(f"FAIL: bogus time_to_recover_s={time_to_recover_s}",
              file=sys.stderr)
        ok = False
    # both paths build one validated g=2 steal3d plan; recovery replaces
    # the host round-trip with a device-side reshard, so it must land in
    # the same ballpark (wide slack: CI wall clocks are noisy)
    if time_to_recover_s > 10.0 * cold_rebuild_s:
        print(f"FAIL: recovery {time_to_recover_s:.3f}s vs cold rebuild "
              f"{cold_rebuild_s:.3f}s exceeds 10x slack", file=sys.stderr)
        ok = False
    if "replan.recoveries" not in replan_metrics:
        print("FAIL: replan.recoveries missing from obs registry",
              file=sys.stderr)
        ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
