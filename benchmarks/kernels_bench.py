"""Local Pallas kernel micro-bench (interpret mode on CPU) + oracle check.

On real TPU hardware the same harness times the compiled kernels; here
interpret-mode wall time is only a correctness-path proxy, so we also report
the jnp-reference time (the number that matters on CPU) and the kernel's
modelled MXU utilization on v5e.

Also measures:

* the repeated-multiply story of the plan-based API: the same SpMM called
  10 times through one reused MatmulPlan (setup + trace amortized away) vs.
  10 fresh plans (the legacy per-call behaviour);
* the vectorized SpGEMM symbolic phase (``ops.build_pair_lists``): since
  PR 2 a numpy sort-merge join + lexsort, not a python dict-of-lists loop —
  the timing row below tracks it (~11x faster at 5k stored blocks than the
  loop it replaced, with the gap growing in the pair count);
* per-algorithm plan build / multiply / predicted-vs-measured cost, exported
  as JSON by ``benchmarks/run.py --json`` (the perf trajectory baseline).
"""
from __future__ import annotations

import time
from typing import Dict

import numpy as np


def _time(fn, repeats: int = 3) -> float:
    # obs.timed blocks on fn's result before reading the clock (async
    # dispatch can't smear) — the check_api-sanctioned timing helper.
    from repro.obs import timed
    return timed(fn, repeats=repeats, warmup=1)


def _plan_reuse_rows(calls: int = 10):
    import jax.numpy as jnp

    from repro.core import api
    from repro.core.api import DistBSR, DistDense
    from repro.core.bsr import random_sparse

    a_d = random_sparse(256, 256, 0.1, seed=3)
    b = np.random.default_rng(3).standard_normal((256, 64)).astype(np.float32)
    a_h = DistBSR.from_dense(a_d, g=1, block_size=32)
    b_h = DistDense.for_rhs(jnp.asarray(b), a_h)

    plan = api.plan_matmul(a_h, b_h, algorithm="ring_c", impl="ref")
    plan(a_h, b_h).block_until_ready()      # compile once
    t0 = time.perf_counter()
    for _ in range(calls):
        plan(a_h, b_h).block_until_ready()
    t_reuse = (time.perf_counter() - t0) / calls

    t0 = time.perf_counter()
    for _ in range(calls):
        fresh = api.plan_matmul(a_h, b_h, algorithm="ring_c", impl="ref",
                                cache=False)
        fresh(a_h, b_h).block_until_ready()
    t_fresh = (time.perf_counter() - t0) / calls

    return [
        (f"plan,spmm_reuse,{calls}calls", t_reuse * 1e6,
         f"us_per_call;traces={plan.traces}"),
        (f"plan,spmm_fresh,{calls}calls", t_fresh * 1e6,
         f"us_per_call;speedup={t_fresh / max(t_reuse, 1e-12):.1f}x"),
    ]


def _pair_list_rows(nnzb: int = 20_000, nbr: int = 512, nbc: int = 512):
    """Time the vectorized SpGEMM symbolic phase (host-side numpy).

    Hypersparse block grid (~40 matched B blocks per A block) — the output
    pair count, which dominates both the join and the lexsort, stays
    O(nnzb), like a real SpGEMM tile.  The replaced dict-of-lists python
    loop measured ~11x slower at 5k blocks on this harness (and scaled
    with the python-level pair count, not numpy throughput).
    """
    from repro.kernels import ops

    rng = np.random.default_rng(7)
    a_rows = np.sort(rng.integers(0, nbr, nnzb)).astype(np.int32)
    a_cols = rng.integers(0, nbc, nnzb).astype(np.int32)
    b_rows = np.sort(rng.integers(0, nbr, nnzb)).astype(np.int32)
    b_cols = rng.integers(0, nbc, nnzb).astype(np.int32)

    t = _time(lambda: ops.build_pair_lists(
        a_rows, a_cols, nnzb, b_rows, b_cols, nnzb, nbr, nbc), repeats=3)
    n_pairs = ops.build_pair_lists(
        a_rows, a_cols, nnzb, b_rows, b_cols, nnzb, nbr, nbc)[4]
    return [(f"symbolic,build_pair_lists,{nnzb}blk", t * 1e3,
             f"ms;pairs={n_pairs};vectorized=numpy_join+lexsort")]


def _algorithm_rows(smoke: bool = False) -> Dict:
    """Per-algorithm plan build / multiply / predicted cost (g=1, ref impl).

    Returns {"algorithms": {name: {metric: float}}, "auto_selection":
    {"choice": name, "scores": {name: float}}} — timings and the
    auto-selection result are separate keys so trajectory consumers can
    diff the floats without special-casing.
    """
    import jax.numpy as jnp

    from repro.core import api
    from repro.core.api import DistBSR, DistDense
    from repro.core.bsr import random_sparse
    from repro.core.roofline import TPU_V5E

    m = 128 if smoke else 512
    a_d = random_sparse(m, m, 0.08, seed=5)
    b = np.random.default_rng(5).standard_normal((m, 64)).astype(np.float32)
    a_h = DistBSR.from_dense(a_d, g=1, block_size=32)
    b_h = DistDense.for_rhs(jnp.asarray(b), a_h)
    out: Dict[str, Dict[str, float]] = {}
    for alg in api.algorithms():
        t0 = time.perf_counter()
        plan = api.plan_matmul(a_h, b_h, algorithm=alg, impl="ref",
                               cache=False)
        t_build = time.perf_counter() - t0
        t0 = time.perf_counter()
        plan(a_h, b_h).block_until_ready()
        t_first = time.perf_counter() - t0
        t_call = _time(lambda: plan(a_h, b_h).block_until_ready(),
                       repeats=2 if smoke else 5)
        out[alg] = {
            "plan_build_s": t_build,
            "first_call_s": t_first,          # trace + compile + run
            "per_multiply_s": t_call,
            "predicted_s_v5e": plan.predicted_cost(TPU_V5E),
        }
    choice, scores = api.auto_select(a_h, b_h, machine=TPU_V5E)
    return {"algorithms": out,
            "auto_selection": {"choice": choice, "scores": scores}}


def obs_drift_section(smoke: bool = False,
                      trace_path: str = None) -> Dict:
    """Traced bench pass: per-algorithm predicted-vs-measured drift.

    Runs the g=1 geometry twice around a traced window: (A) per-multiply
    with tracing disabled, (B) traced calls — each records a span and a
    drift pair through the normal ``MatmulPlan.__call__`` path — then
    (C) per-multiply with tracing disabled again.  The section reports
    the per-algorithm drift ratios (``obs.drift_report()``), the trace's
    schema validity, and asserts the disabled path stayed within noise
    of the never-traced one (A vs C) — tracing must cost nothing when
    off.  ``trace_path`` additionally writes the Chrome trace JSON.
    """
    import jax.numpy as jnp

    from repro import obs
    from repro.core import api
    from repro.core.api import DistBSR, DistDense
    from repro.core.bsr import random_sparse

    m = 128 if smoke else 512
    a_d = random_sparse(m, m, 0.08, seed=5)
    b = np.random.default_rng(5).standard_normal((m, 64)).astype(np.float32)
    a_h = DistBSR.from_dense(a_d, g=1, block_size=32)
    b_h = DistDense.for_rhs(jnp.asarray(b), a_h)
    algs = ("ring_c", "summa_bcast") if smoke else tuple(api.algorithms())
    reps = 3 if smoke else 5
    plans = {}
    for alg in algs:
        plans[alg] = api.plan_matmul(a_h, b_h, algorithm=alg, impl="ref",
                                     cache=False)
        plans[alg](a_h, b_h).block_until_ready()   # compile before timing
    before = {alg: _time(lambda p=p: p(a_h, b_h).block_until_ready(),
                         repeats=reps) for alg, p in plans.items()}
    obs.enable(clear=True, drift=True)
    obs.reset_drift()
    with obs.span("bench.obs_drift", smoke=smoke):
        # one plan build under tracing so the exported trace carries
        # plan-build spans next to the per-multiply ones
        api.plan_matmul(a_h, b_h, algorithm=algs[0], impl="ref",
                        cache=False)
        for p in plans.values():
            for _ in range(reps):
                p(a_h, b_h)
    obs.disable()
    report = obs.drift_report()
    trace = obs.export_trace(trace_path)
    problems = obs.validate_trace(trace)
    after = {alg: _time(lambda p=p: p(a_h, b_h).block_until_ready(),
                        repeats=reps) for alg, p in plans.items()}
    # Disabled-mode overhead gate: total per-multiply time after the traced
    # window (tracing off again) must sit within noise of the never-traced
    # baseline.  Generous slack — fake-device CPU timings jitter — but a
    # forgotten always-on clock/block would blow well past it.
    t_before = sum(before.values())
    t_after = sum(after.values())
    overhead_ok = t_after <= t_before * 1.5 + 5e-3
    drift = {alg: report[key] for alg in algs
             if (key := f"{alg}/{plans[alg].wire}/auto") in report}
    return {
        "drift": drift,
        "trace_events": len(trace["traceEvents"]),
        "trace_valid": not problems,
        "trace_problems": problems[:10],
        "span_names": sorted({e["name"] for e in trace["traceEvents"]}),
        "per_multiply_untraced_s": before,
        "per_multiply_after_disable_s": after,
        "disabled_overhead_ok": bool(overhead_ok),
    }


def run(repeats: int = 3, smoke: bool = False):
    import jax.numpy as jnp

    from repro.core.bsr import BSR, random_sparse
    from repro.kernels import ops

    rows = []
    cases = ((256, 256, 256, 32, 0.1),) if smoke else \
        ((256, 256, 256, 32, 0.1), (512, 512, 128, 64, 0.05))
    for m, k, n, bs, dens in cases:
        a_d = random_sparse(m, k, dens, seed=0)
        b = np.random.default_rng(0).standard_normal((k, n)).astype(
            np.float32)
        a = BSR.from_dense(a_d, bs)
        b_j = jnp.asarray(b)

        ref = lambda: ops.bsr_spmm(a, b_j, impl="ref").block_until_ready()
        ref()
        t0 = time.perf_counter()
        for _ in range(repeats):
            ref()
        t_ref = (time.perf_counter() - t0) / repeats
        err = float(np.abs(np.asarray(ops.bsr_spmm(a, b_j, impl="interpret",
                                                   block_n=min(n, 128)))
                           - a_d @ b).max())
        flops = a.flops(n)
        rows.append((f"kernel,bsr_spmm,{m}x{k}x{n},bs={bs},d={dens}",
                     t_ref * 1e6,
                     f"us_ref;pallas_err={err:.1e};"
                     f"mxu_s_v5e={flops / 197e12:.2e}"))
    rows.extend(_pair_list_rows(*((2_000, 256, 256) if smoke
                                  else (20_000, 512, 512))))
    if not smoke:
        rows.extend(_plan_reuse_rows())
    return rows


def run_json(smoke: bool = False) -> Dict:
    """Structured results for BENCH_kernels.json (see benchmarks/run.py)."""
    return {
        "csv_rows": [list(r) for r in run(repeats=1 if smoke else 3,
                                          smoke=smoke)],
        "algorithms_g1": _algorithm_rows(smoke=smoke),
    }


def _obs_smoke() -> bool:
    """Traced obs pass for ``--smoke``: exports a Chrome trace,
    schema-validates it, reports per-algorithm drift ratios, and checks
    that tracing disabled leaves per-multiply timings within noise."""
    import os
    import tempfile
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as tf:
        trace_path = tf.name
    try:
        sec = obs_drift_section(smoke=True, trace_path=trace_path)
    finally:
        os.unlink(trace_path)
    ok = sec["trace_valid"] and sec["disabled_overhead_ok"] \
        and bool(sec["drift"])
    ratios = ";".join(f"{a}={d['ratio']:.1f}"
                      for a, d in sorted(sec["drift"].items()))
    print(f"smoke,obs_trace,{'ok' if ok else 'FAILED'};"
          f"events={sec['trace_events']};{ratios}")
    return ok


def main(argv=None) -> int:
    """``--json`` prints one JSON object (the ``kernels`` and
    ``obs_drift`` sections of BENCH_kernels.json); otherwise CSV rows,
    plus the traced obs check under ``--smoke``."""
    import json
    import sys
    argv = sys.argv[1:] if argv is None else argv
    smoke = "--smoke" in argv
    if "--json" in argv:
        print(json.dumps({"kernels": run_json(smoke=smoke),
                          "obs_drift": obs_drift_section(smoke=smoke)}))
        return 0
    for name, val, unit in run(smoke=smoke):
        print(f"{name},{val:.1f},{unit}")
    return 0 if not smoke or _obs_smoke() else 1


if __name__ == "__main__":
    raise SystemExit(main())
