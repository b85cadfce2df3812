"""Static analysis benchmark: verifier wall time on healthy plans.

Runs the full static verifier (``repro.analysis``) over the plan matrix
the other benchmarks execute — every registered algorithm through padded
and packed wire on a skewed R-MAT SpMM, plus a sparse-output SpGEMM —
and records per-plan wall time for the schedule checker and the jaxpr
lint, asserting **zero findings** on every healthy plan (the clean-plan
contract the mutation tests invert).

Also measures the acceptance criterion for the ``validate=`` plumbing:
on a *cached* plan, ``plan_matmul(validate="fast")`` must add < 5% over
``validate="off"`` — the per-plan verdict is memoized, so a cache hit
pays one set lookup, not a re-verification.

Runs in its own process (16 fake CPU devices must be configured before
jax imports).  Prints a single JSON object; ``benchmarks/run.py --json``
embeds it as the ``analysis`` section of BENCH_kernels.json and the
``--smoke`` tier-1 path asserts the zero-findings + overhead contract.

Usage:  python -m benchmarks.analysis_bench [--scale 11] [--smoke]
"""
from __future__ import annotations

import argparse
import json
import sys
import time

DEVICES = 16  # 4x4 grid

ALGORITHMS = ("ring_c", "ring_a", "ring_c_bidir", "summa_ag",
              "summa_bcast", "steal3d")


def main() -> int:  # analysis: allow(source.perf-counter-discipline)
    p = argparse.ArgumentParser()
    p.add_argument("--scale", type=int, default=11)
    p.add_argument("--n-cols", type=int, default=256)
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--repeats", type=int, default=5)
    p.add_argument("--smoke", action="store_true",
                   help="scale-8 quick pass")
    args = p.parse_args()
    if args.smoke:
        args.scale, args.repeats = 8, 3
        args.block_size, args.n_cols = 8, 64

    from repro.runtime.platform import set_host_device_count
    set_host_device_count(DEVICES)
    import jax.numpy as jnp  # noqa: E402  (after flag setup)
    import numpy as np

    from repro import analysis
    from repro.core import api
    from repro.core.api import DistBSR, DistDense
    from repro.core.bsr import rmat_matrix
    from repro.core.dist import make_grid_mesh

    g = 4
    a_dense = rmat_matrix(scale=args.scale, edgefactor=8, seed=0)
    b = np.random.default_rng(0).standard_normal(
        (a_dense.shape[1], args.n_cols)).astype(np.float32)
    mesh = make_grid_mesh(g)
    a_h = DistBSR.from_dense(a_dense, g=g, block_size=args.block_size)
    b_h = DistDense.for_rhs(jnp.asarray(b), a_h)

    out = {"rmat_scale": args.scale, "g": g,
           "block_size": args.block_size, "n_cols": args.n_cols,
           "plans": {}}
    failures = []
    total_findings = 0

    def verify(tag, plan, lhs, rhs):  # analysis: allow(source.perf-counter-discipline)
        nonlocal total_findings
        t0 = time.perf_counter()
        f_sched = analysis.check_plan(plan, lhs, rhs)
        t_sched = time.perf_counter() - t0
        t0 = time.perf_counter()
        jaxpr = analysis.trace_plan(plan, lhs, rhs)
        f_lint = analysis.lint_plan(plan, jaxpr=jaxpr)
        t_lint = time.perf_counter() - t0
        found = f_sched + f_lint
        out["plans"][tag] = {
            "schedule_check_s": t_sched,
            "jaxpr_lint_s": t_lint,
            "findings": len(found),
        }
        total_findings += len(found)
        for f in found:
            failures.append(f"{tag}: {f}")

    api.clear_plan_cache()
    for alg in ALGORITHMS:
        for wire in ("padded", "packed"):
            plan = api.plan_matmul(a_h, b_h, mesh=mesh, algorithm=alg,
                                   impl="ref", wire=wire, cache=False)
            verify(f"{alg}/{wire}", plan, a_h, b_h)
    plan = api.plan_matmul(a_h, a_h, mesh=mesh, algorithm="ring_c",
                           impl="ref", output="sparse", cache=False)
    verify("ring_c/sparse-output", plan, a_h, a_h)

    # cached plan-build overhead of validate="fast": warm the plan cache
    # and the per-plan verdict memo, then time pure cache-hit rebuilds.
    # Modes are interleaved per trial (min-of-trials each) so host load
    # drift during the run cannot land on one side of the comparison.
    api.clear_plan_cache()
    kw = dict(mesh=mesh, algorithm="ring_c", impl="ref")
    api.plan_matmul(a_h, b_h, validate="fast", **kw)   # warm both caches
    n_calls = 500

    def hit_times():  # analysis: allow(source.perf-counter-discipline)
        samples = {"off": [], "fast": []}
        for _ in range(max(args.repeats, 5) * 2):
            for mode in ("off", "fast"):
                t0 = time.perf_counter()
                for _ in range(n_calls):
                    api.plan_matmul(a_h, b_h, validate=mode, **kw)
                samples[mode].append(
                    (time.perf_counter() - t0) / n_calls)
        return samples

    samples = hit_times()
    t_off, t_fast = min(samples["off"]), min(samples["fast"])
    # overhead as the median of paired per-trial ratios: pairing cancels
    # host load drift across the run, the median kills preemption spikes
    ratios = sorted(f / o for o, f in zip(samples["off"],
                                          samples["fast"]) if o)
    overhead = ratios[len(ratios) // 2] - 1.0 if ratios else float("inf")
    out["validate_fast"] = {
        "cached_build_s_off": t_off,
        "cached_build_s_fast": t_fast,
        "overhead": overhead,
        "overhead_ok": overhead < 0.05,
    }
    if overhead >= 0.05:
        failures.append(
            f"validate='fast' adds {overhead:.1%} to cached plan build "
            "(contract: < 5%)")

    out["total_findings"] = total_findings
    out["clean"] = total_findings == 0
    json.dump(out, sys.stdout, indent=1)
    print()
    if failures:
        print("analysis_bench FAILED: " + "; ".join(failures),
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
