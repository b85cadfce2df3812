"""Overlap A-B benchmark: double-buffered vs bulk-synchronous schedules.

The acceptance experiment for the asynchronous-overlap subsystem: an
unpermuted skewed R-MAT SpMM on a 4x4 grid, every registered schedule
built twice through ``plan_matmul`` — once with ``overlap="on"``
(split-step double-buffered bodies: step i+1's ``ppermute`` issued before
step i's accumulate) and once with ``overlap="off"`` (the legacy bulk
scan, each transfer fully exposed).  Per schedule it records both
per-multiply times and ``comm_exposed = max(0, measured - t_comp)`` —
the communication left visible above the host roofline's compute floor,
the quantity the overlap term of the cost model
(``exposed = max(0, t_comm - overlap_eff * t_comp)``) predicts and
``tools/fit_machine.py`` fits ``Machine.overlap_eff`` from.

The run *asserts* the overlap contract — double-buffered results allclose
to bulk for every schedule, and exposed comm no worse than bulk beyond
measurement tolerance — and exits non-zero on violation, so the
``--smoke`` tier-1 path enforces it in CI.  (On the fake-device CPU
harness XLA runs collectives synchronously, so "no worse" plus the byte
parity recorded here is the honest claim; the GPU async-collective flags
that realize the hiding are planted by ``repro.runtime.platform``.)

Runs in its own process (16 fake CPU devices must be configured before
jax imports).  Prints a single JSON object; ``benchmarks/run.py --json``
embeds it in BENCH_kernels.json.

Usage:  python -m benchmarks.overlap_bench [--scale 11] [--smoke]
"""
from __future__ import annotations

import argparse
import json
import sys
import time

DEVICES = 16  # 4x4 grid

# measurement tolerance for the "overlap never worse" assert: min-of-repeats
# on 16 fake CPU devices still jitters by scheduling noise
SLACK_FACTOR = 1.25
SLACK_ABS_S = 5e-3

# Schedules whose overlap="on" form adds a kernel dispatch (steal3d's
# own/stolen segment split) rather than reordering a scan: on this
# synchronous-collective harness the extra dispatch is pure overhead
# (which is why plan_matmul keeps their "auto" at the bulk body), so
# they are A-B *recorded* here but exempt from the regression assert.
SEGMENT_SPLIT_ALGS = frozenset({"steal3d"})


# obs.timed blocks on fn's result before reading the clock (async
# dispatch can't smear) — the check_api-sanctioned timing helper.
from repro.obs import timed as _timed  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    # Same geometry as steal_bench/wire_bench: scale-11 R-MAT, 256 dense
    # columns, bs=16 — per-step einsums well above the shard_map dispatch
    # floor, so body-structure differences (not fixed overheads) dominate.
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
    from repro.core.roofline import HOST_CPU, TPU_V5E

    g = 4
    a_dense = rmat_matrix(scale=args.scale, edgefactor=8, seed=0)
    b = np.random.default_rng(0).standard_normal(
        (a_dense.shape[1], args.n_cols)).astype(np.float32)
    mesh = make_grid_mesh(g)
    a_h = DistBSR.from_dense(a_dense, g=g, block_size=args.block_size)
    b_h = DistDense.for_rhs(jnp.asarray(b), a_h)

    out = {"rmat_scale": args.scale, "g": g,
           "block_size": args.block_size, "n_cols": args.n_cols,
           "a_capacity": a_h.capacity, "algorithms": {}}

    api.clear_plan_cache()
    failures = []
    plans = {}
    # Phase 1: build + warm every (algorithm, overlap) plan.
    for alg in api.algorithms():
        for overlap in ("on", "off"):
            t0 = time.perf_counter()
            plan = api.plan_matmul(a_h, b_h, mesh=mesh, algorithm=alg,
                                   impl="ref", overlap=overlap, cache=False)
            t_build = time.perf_counter() - t0
            c = plan(a_h, b_h)
            c.block_until_ready()
            plans[alg, overlap] = (plan, np.asarray(c), t_build)

    # Phase 2: steady-state timing, variants interleaved per repeat;
    # min over repeats (scheduling noise on 16 fake devices swamps a mean).
    times = {key: [] for key in plans}
    for _ in range(args.repeats):
        for key, (plan, _c, _t) in plans.items():
            times[key].append(
                _timed(lambda p=plan: p(a_h, b_h).block_until_ready()))

    for alg in api.algorithms():
        plan_on, c_on, tb_on = plans[alg, "on"]
        plan_off, c_off, tb_off = plans[alg, "off"]
        # compute floor from the harness machine's roofline: everything
        # measured above it is exposed communication + dispatch
        t_comp = plan_on.predicted_perf(HOST_CPU)["t_comp"]
        t_on = min(times[alg, "on"])
        t_off = min(times[alg, "off"])
        exposed_on = max(0.0, t_on - t_comp)
        exposed_off = max(0.0, t_off - t_comp)
        allclose = bool(np.allclose(c_on, c_off, atol=1e-4))
        out["algorithms"][alg] = {
            "plan_build_s_on": tb_on,
            "plan_build_s_off": tb_off,
            "per_multiply_s_on": t_on,
            "per_multiply_s_off": t_off,
            "t_comp_host_s": t_comp,
            "comm_exposed_on_s": exposed_on,
            "comm_exposed_off_s": exposed_off,
            "predicted_s_v5e_on": plan_on.predicted_cost(TPU_V5E),
            "predicted_s_v5e_off": plan_off.predicted_cost(TPU_V5E),
            "overlap_eff_scored_on":
                plan_on.predicted_perf(TPU_V5E)["overlap_eff"],
            "allclose_on_vs_off": allclose,
        }
        if not allclose:
            failures.append(f"{alg}: overlap=on result diverges from off")
        if alg not in SEGMENT_SPLIT_ALGS and \
                exposed_on > exposed_off * SLACK_FACTOR + SLACK_ABS_S:
            failures.append(
                f"{alg}: exposed comm regressed with overlap on "
                f"({exposed_on:.4f}s vs {exposed_off:.4f}s off)")

    # what the auto-scheduler does with and without overlap credit
    choice_auto, scores_auto = api.auto_select(a_h, b_h, machine=TPU_V5E,
                                               overlap="auto")
    choice_off, scores_off = api.auto_select(a_h, b_h, machine=TPU_V5E,
                                             overlap="off")
    out["auto"] = {"choice_v5e_overlap_auto": choice_auto,
                   "choice_v5e_overlap_off": choice_off,
                   "scores_v5e_overlap_auto": scores_auto,
                   "scores_v5e_overlap_off": scores_off}

    out["overlap_never_worse"] = not any("regressed" in f for f in failures)
    json.dump(out, sys.stdout, indent=1)
    print()
    if failures:
        print("overlap_bench FAILED: " + "; ".join(failures),
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
