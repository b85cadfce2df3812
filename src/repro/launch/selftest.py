"""Multi-device correctness self-test (run as a subprocess).

Plants the fake-device XLA flags (via ``repro.runtime.platform``) *before*
the first jax backend init, builds a small host-device mesh,
and checks the distributed algorithms against dense references.  Used by
``tests/test_distributed.py`` and as a launch-time preflight on real
clusters (a node that fails its self-test is drained before training
starts — part of the fault-tolerance story).

All distributed-matmul checks go through the plan-based API
(:mod:`repro.core.api`); the ``api`` check additionally verifies plan/
placement reuse (no re-trace, skew applied once) and that the deprecated
``core.spmm`` shims are bit-identical to the planned path.

Usage:  python -m repro.launch.selftest --devices 4 --check all
"""
from __future__ import annotations

import argparse
import sys


def _parse():
    p = argparse.ArgumentParser()
    p.add_argument("--devices", type=int, default=4)
    p.add_argument("--check", default="all",
                   choices=["all", "spmm", "spgemm", "spgemm_sparse",
                            "dense", "api", "balance", "steal3d", "wire",
                            "moe", "train_parallel", "obs", "analysis",
                            "elastic"])
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args()


def main() -> int:
    args = _parse()
    from repro.runtime.platform import (enable_compile_cache,
                                        set_host_device_count)
    set_host_device_count(args.devices)
    enable_compile_cache()
    import jax  # noqa: E402  (after flag setup)
    import jax.numpy as jnp
    import numpy as np

    from repro.core import api, schedules
    from repro.core.api import DistBSR, DistDense
    from repro.core.bsr import random_sparse
    from repro.core.dist import make_grid_mesh

    needs_grid = args.check in ("all", "dense", "spmm", "spgemm",
                                "spgemm_sparse", "api", "balance",
                                "steal3d", "wire", "analysis")
    g = int(np.sqrt(args.devices))
    mesh = None
    if needs_grid:
        assert g * g == args.devices, "grid checks need a square device count"
        mesh = make_grid_mesh(g)
    rng = np.random.default_rng(args.seed)
    failures = []

    def check(name, got, want, tol=1e-4):
        err = float(np.max(np.abs(np.asarray(got) - np.asarray(want))))
        ok = err <= tol
        print(f"  [{'ok' if ok else 'FAIL'}] {name:28s} max|err|={err:.3e}")
        if not ok:
            failures.append(name)

    def check_flag(name, ok):
        print(f"  [{'ok' if ok else 'FAIL'}] {name}")
        if not ok:
            failures.append(name)

    if args.check in ("all", "dense"):
        print(f"== dense matmul on {g}x{g} mesh ==")
        # odd shapes exercise the shared pad/crop epilogue on the dense path
        a = rng.standard_normal((23, 19)).astype(np.float32)
        b = rng.standard_normal((19, 11)).astype(np.float32)
        want = a @ b
        for alg in api.algorithms():
            got = api.matmul(jnp.asarray(a), jnp.asarray(b), g=g, mesh=mesh,
                             algorithm=alg)
            check(f"dense/{alg}", got, want)

    if args.check in ("all", "spmm"):
        print(f"== spmm on {g}x{g} mesh ==")
        a_d = random_sparse(32, 32, 0.2, seed=args.seed)
        b = rng.standard_normal((32, 8)).astype(np.float32)
        a_h = DistBSR.from_dense(a_d, g=g, block_size=4)
        b_h = DistDense.for_rhs(jnp.asarray(b), a_h)
        want = a_d @ b
        for alg in api.algorithms():
            got = api.matmul(a_h, b_h, mesh=mesh, algorithm=alg, impl="ref")
            check(f"spmm/{alg}", got, want)
        # Pallas interpret path through the distributed ring
        got = api.matmul(a_h, b_h, mesh=mesh, algorithm="ring_c",
                         impl="interpret")
        check("spmm/ring_c[interpret]", got, want)

    if args.check in ("all", "spgemm"):
        print(f"== spgemm on {g}x{g} mesh ==")
        a_d = random_sparse(32, 32, 0.15, seed=args.seed + 1)
        b_d = random_sparse(32, 32, 0.2, seed=args.seed + 2)
        a_h = DistBSR.from_dense(a_d, g=g, block_size=4)
        b_h = DistBSR.from_dense(b_d, g=g, block_size=4)
        want = a_d @ b_d
        for alg in api.algorithms():
            got = api.matmul(a_h, b_h, mesh=mesh, algorithm=alg, impl="ref")
            check(f"spgemm/{alg}", got, want)

    if args.check in ("all", "spgemm_sparse"):
        print(f"== sparse-output spgemm on {g}x{g} mesh ==")
        a_d = random_sparse(32, 32, 0.15, seed=args.seed + 4)
        b_d = random_sparse(32, 32, 0.2, seed=args.seed + 5)
        a_h = DistBSR.from_dense(a_d, g=g, block_size=4)
        b_h = DistBSR.from_dense(b_d, g=g, block_size=4)
        want = a_d @ b_d
        for alg in api.sparse_algorithms():
            c = api.matmul(a_h, b_h, mesh=mesh, algorithm=alg, impl="ref",
                           output="sparse")
            check(f"spgemm_sparse/{alg}", c.densify(), want)
        check_flag("spgemm_sparse/returns_handle",
                   isinstance(api.matmul(a_h, b_h, mesh=mesh,
                                         algorithm="ring_c", impl="ref",
                                         output="sparse"), DistBSR))
        # chained cube stays packed: the product handle is the operand
        c2 = api.matmul(a_h, a_h, mesh=mesh, algorithm="ring_c", impl="ref",
                        output="sparse")
        c3 = api.matmul(c2, a_h, mesh=mesh, algorithm="ring_c", impl="ref",
                        output="sparse")
        check("spgemm_sparse/chain_cube", c3.densify(), a_d @ a_d @ a_d,
              tol=1e-3)
        # Pallas interpret path through the packed ring
        c_i = api.matmul(a_h, b_h, mesh=mesh, algorithm="ring_c",
                         impl="interpret", output="sparse")
        check("spgemm_sparse/ring_c[interpret]", c_i.densify(), want)

    if args.check in ("all", "balance"):
        print(f"== balanced tiling + auto-scheduling on {g}x{g} mesh ==")
        from repro.core.bsr import rmat_matrix
        a_d = rmat_matrix(scale=6, edgefactor=8, seed=args.seed)  # skewed
        b = rng.standard_normal((64, 8)).astype(np.float32)
        b_j = jnp.asarray(b)
        h_none = DistBSR.from_dense(a_d, g=g, block_size=4)
        h_rows = DistBSR.from_dense(a_d, g=g, block_size=4, balance="rows")
        check_flag(
            f"balance/capacity ({h_rows.capacity} <= {h_none.capacity})",
            h_rows.capacity <= h_none.capacity)
        want = a_d @ b
        b_h = DistDense.for_rhs(b_j, h_rows)
        for alg in api.algorithms():
            got = api.matmul(h_rows, b_h, mesh=mesh, algorithm=alg,
                             impl="ref")
            check(f"balance/{alg}", got, want)
        plan = api.plan_matmul(h_rows, b_h, mesh=mesh, algorithm="auto",
                               impl="ref")
        check(f"balance/auto[{plan.algorithm.name}]", plan(h_rows, b_h),
              want)
        check_flag("balance/auto_scores_recorded",
                   plan.auto_scores is not None and
                   plan.algorithm.name == min(plan.auto_scores,
                                              key=plan.auto_scores.get))

    if args.check in ("all", "steal3d"):
        print(f"== steal3d static work-grid dispatch on {g}x{g} mesh ==")
        from repro.core.bsr import rmat_matrix
        a_d = rmat_matrix(scale=6, edgefactor=8, seed=args.seed)  # skewed
        b = rng.standard_normal((64, 8)).astype(np.float32)
        b_sp = random_sparse(64, 64, 0.1, seed=args.seed + 6)
        a_h = DistBSR.from_dense(a_d, g=g, block_size=4)
        b_h = DistDense.for_rhs(jnp.asarray(b), a_h)
        b_sph = DistBSR.from_dense(b_sp, g=g, block_size=4)
        plan = api.plan_matmul(a_h, b_h, mesh=mesh, algorithm="steal3d",
                               impl="ref")
        asg = plan.steal.assignment
        check_flag(
            f"steal3d/makespan<=owner ({asg.makespan:.0f} <= "
            f"{asg.owner_makespan:.0f}, moved={asg.n_moved})",
            asg.makespan <= asg.owner_makespan)
        check("steal3d/spmm", plan(a_h, b_h), a_d @ b)
        check("steal3d/spmm_vs_ring_c", plan(a_h, b_h),
              api.matmul(a_h, b_h, mesh=mesh, algorithm="ring_c",
                         impl="ref"))
        check("steal3d/spgemm",
              api.matmul(a_h, b_sph, mesh=mesh, algorithm="steal3d",
                         impl="ref"), a_d @ b_sp)
        da = rng.standard_normal((23, 19)).astype(np.float32)
        db = rng.standard_normal((19, 11)).astype(np.float32)
        check("steal3d/dense",
              api.matmul(jnp.asarray(da), jnp.asarray(db), g=g, mesh=mesh,
                         algorithm="steal3d"), da @ db)
        # Pallas interpret path through the pooled pair-accumulate kernel
        check("steal3d/spmm[interpret]",
              api.matmul(a_h, b_h, mesh=mesh, algorithm="steal3d",
                         impl="interpret"), a_d @ b)
        # empty operand fast path (capacity 0) end-to-end (satellite)
        e_h = DistBSR.from_dense(np.zeros((64, 64), np.float32), g=g,
                                 block_size=4)
        check_flag(f"steal3d/empty_capacity_0 (cap={e_h.capacity})",
                   e_h.capacity == 0)
        check("steal3d/empty_operand",
              api.matmul(e_h, b_h, mesh=mesh, algorithm="steal3d",
                         impl="ref"), np.zeros((64, 8), np.float32))

    if args.check in ("all", "wire"):
        print(f"== packed wire format on {g}x{g} mesh ==")
        from repro.core.bsr import rmat_matrix
        a_d = rmat_matrix(scale=6, edgefactor=8, seed=args.seed)  # skewed
        b = rng.standard_normal((64, 8)).astype(np.float32)
        b_sp = random_sparse(64, 64, 0.08, seed=args.seed + 9)
        a_h = DistBSR.from_dense(a_d, g=g, block_size=4)
        b_h = DistDense.for_rhs(jnp.asarray(b), a_h)
        b_sph = DistBSR.from_dense(b_sp, g=g, block_size=4)
        for alg in api.algorithms():
            plan = api.plan_matmul(a_h, b_h, mesh=mesh, algorithm=alg,
                                   impl="ref", wire="packed")
            check(f"wire/spmm/{alg}[{plan.wire}]", plan(a_h, b_h), a_d @ b)
            plan_sp = api.plan_matmul(a_h, b_sph, mesh=mesh, algorithm=alg,
                                      impl="ref", wire="packed")
            check(f"wire/spgemm/{alg}[{plan_sp.wire}]", plan_sp(a_h, b_sph),
                  a_d @ b_sp)
            if plan.wire == "packed":
                pad = api.plan_matmul(a_h, b_h, mesh=mesh, algorithm=alg,
                                      impl="ref", wire="padded")
                bp = plan.cost_model()["total_net_bytes"]
                bd = pad.cost_model()["total_net_bytes"]
                check_flag(f"wire/bytes/{alg} ({bp:.0f} <= {bd:.0f})",
                           bp <= bd)
        for alg in api.sparse_algorithms():
            plan = api.plan_matmul(a_h, b_sph, mesh=mesh, algorithm=alg,
                                   impl="ref", output="sparse")
            check_flag(f"wire/sparse_output/{alg}_auto_packs",
                       plan.wire == "packed")
            check(f"wire/sparse_output/{alg}", plan(a_h, b_sph).densify(),
                  a_d @ b_sp)
        # interpret impl drives the pallas-path kernels over packed buffers
        check("wire/spmm/ring_c[interpret]",
              api.matmul(a_h, b_h, mesh=mesh, algorithm="ring_c",
                         impl="interpret", wire="packed"), a_d @ b)

    if args.check in ("all", "api"):
        print(f"== plan-based API invariants on {g}x{g} mesh ==")
        from repro.core import spmm as legacy
        a_d = random_sparse(32, 32, 0.2, seed=args.seed + 3)
        b = rng.standard_normal((32, 8)).astype(np.float32)
        b_j = jnp.asarray(b)
        a_h = DistBSR.from_dense(a_d, g=g, block_size=4)
        b_h = DistDense.for_rhs(b_j, a_h)
        api.clear_plan_cache()
        plan = api.plan_matmul(a_h, b_h, mesh=mesh, algorithm="ring_c",
                               impl="ref")
        outs = [plan(a_h, b_h) for _ in range(5)]
        check("api/plan_result", outs[-1], a_d @ b)
        check_flag(f"api/plan_traces_once (traces={plan.traces})",
                   plan.traces == 1)
        check_flag("api/placement_cached",
                   a_h.placed("skew_rows") is a_h.placed("skew_rows"))
        got_new = api.matmul(a_h, b_h, mesh=mesh, algorithm="ring_c",
                             impl="ref")
        import warnings as _w
        with _w.catch_warnings():
            _w.simplefilter("ignore", DeprecationWarning)
            got_old = legacy.spmm(a_h.tiled, b_j, mesh=mesh,
                                  algorithm="ring_c", impl="ref")
        check_flag("api/shim_bit_identical",
                   bool((np.asarray(got_new) == np.asarray(got_old)).all()))
        check_flag(f"api/shared_plan_cache (size={api.plan_cache_size()})",
                   api.plan_cache_size() == 1)

    if args.check in ("all", "analysis"):
        print(f"== static plan verification on {g}x{g} mesh ==")
        import dataclasses as _dc

        from repro import analysis
        from repro.core.bsr import rmat_matrix
        a_d = rmat_matrix(scale=6, edgefactor=8, seed=args.seed)  # skewed
        b = rng.standard_normal((64, 8)).astype(np.float32)
        b_sp = random_sparse(64, 64, 0.1, seed=args.seed + 7)
        a_h = DistBSR.from_dense(a_d, g=g, block_size=4)
        b_h = DistDense.for_rhs(jnp.asarray(b), a_h)
        b_sph = DistBSR.from_dense(b_sp, g=g, block_size=4)
        # healthy plans across the dispatch matrix prove clean — the
        # collective-count rule only has teeth at g >= 2, so this is the
        # multi-device leg of the coverage tests
        combos = []
        for alg in api.algorithms():
            for wirem in ("padded", "packed"):
                for ov in ("off", "on"):
                    combos.append((alg, b_h, "dense", wirem, ov))
            combos.append((alg, b_sph, "dense", "padded", "off"))
        for alg in api.sparse_algorithms():
            combos.append((alg, b_sph, "sparse", "packed", "off"))
        n_findings = 0
        for alg, rhs, out, wirem, ov in combos:
            plan = api.plan_matmul(a_h, rhs, mesh=mesh, algorithm=alg,
                                   impl="ref", output=out, wire=wirem,
                                   overlap=ov)
            fs = analysis.check_plan(plan, a_h, rhs) \
                + analysis.lint_plan(plan, a_h, rhs)
            for f in fs:
                print(f"    finding [{alg}/{out}/{wirem}/ov={ov}]: {f}")
            n_findings += len(fs)
        check_flag(f"analysis/healthy_matrix_clean ({len(combos)} plans)",
                   n_findings == 0)
        # validate= plumbing: full verification passes and is memoized
        plan = api.plan_matmul(a_h, b_h, mesh=mesh, algorithm="ring_c",
                               impl="ref", validate="full")
        check_flag("analysis/validate_full_passes",
                   "full" in plan._validated and "fast" in plan._validated)
        # n_msgs drift: a schedule charging the wrong message count must
        # be caught by jaxpr.collective-count (needs g >= 2: at g == 1
        # the ring perms degenerate and message groups alias)
        bad = _dc.replace(api.REGISTRY.get("ring_c"), name="bad_msgs",
                          msgs_per_step=7)
        api.REGISTRY.register(bad)
        try:
            plan = api.plan_matmul(a_h, b_h, mesh=mesh,
                                   algorithm="bad_msgs", impl="ref",
                                   cache=False)
            fs = analysis.lint_plan(plan, a_h, b_h)
            check_flag("analysis/collective_count_drift_caught",
                       any(f.rule == "jaxpr.collective-count"
                           for f in fs))
            raised = False
            try:
                api.plan_matmul(a_h, b_h, mesh=mesh, algorithm="bad_msgs",
                                impl="ref", cache=False, validate="full")
            except analysis.PlanValidationError as e:
                raised = any(f.rule == "jaxpr.collective-count"
                             for f in e.findings)
            check_flag("analysis/validate_full_raises_on_drift", raised)
        finally:
            api.REGISTRY.unregister("bad_msgs")
        # corrupted ring permutation at real grid size
        plan = api.plan_matmul(a_h, b_h, mesh=mesh, algorithm="ring_c",
                               impl="ref", cache=False)
        orig_perm = schedules._ring_perm
        schedules._ring_perm = lambda gg, sign=1: tuple(
            ((d + sign) % gg, 0) for d in range(gg))   # all -> device 0
        try:
            fs = analysis.check_plan(plan, a_h, b_h)
        finally:
            schedules._ring_perm = orig_perm
        check_flag("analysis/corrupt_perm_caught",
                   any(f.rule == "schedule.ppermute-bijection"
                       for f in fs))

    if args.check in ("all", "moe"):
        print("== MoE dispatch/combine vs dense ==")
        from repro.models import moe as moe_mod
        ok = moe_mod.selftest_distributed(args.devices)
        print(f"  [{'ok' if ok else 'FAIL'}] moe/expert_parallel")
        if not ok:
            failures.append("moe")
        ok = moe_mod.selftest_ring(args.devices)
        print(f"  [{'ok' if ok else 'FAIL'}] moe/ring_dispatch")
        if not ok:
            failures.append("moe_ring")

    if args.check in ("all", "obs"):
        print("== execution tracing + drift tracking ==")
        import json as _json
        import os as _os
        import tempfile as _tempfile

        from repro import obs
        a_d = random_sparse(32, 32, 0.2, seed=args.seed + 6)
        b = rng.standard_normal((32, 8)).astype(np.float32)
        a_h = DistBSR.from_dense(a_d, g=1, block_size=4)
        b_h = DistDense.for_rhs(jnp.asarray(b), a_h)
        obs.enable(clear=True, drift=True)
        obs.reset_drift()
        plan = api.plan_matmul(a_h, b_h, algorithm="ring_c", impl="ref",
                               cache=False)
        for _ in range(3):
            out = plan(a_h, b_h)
        obs.disable()
        check("obs/traced_result", out, a_d @ b)
        names = {e["name"] for e in obs.events()}
        check_flag("obs/plan_build_span", "plan_build" in names)
        check_flag("obs/multiply_span", "multiply.ring_c" in names)
        fd, path = _tempfile.mkstemp(suffix=".json")
        _os.close(fd)
        try:
            obs.export_trace(path)
            with open(path) as f:
                trace = _json.load(f)
        finally:
            _os.unlink(path)
        check_flag("obs/trace_schema_valid",
                   not obs.validate_trace(trace))
        drift = obs.drift_report()
        check_flag(f"obs/drift_recorded ({len(drift)} keys)",
                   any(d["n"] >= 3 for d in drift.values()))
        check_flag("obs/disabled_is_noop",
                   obs.span("x") is obs.span("y"))

    if args.check == "elastic" or (args.check == "all" and args.devices >= 9):
        # needs 9 devices: builds its own 3x3 (pre-loss) and 2x2 meshes,
        # so it is deliberately outside the needs_grid square assertion
        print("== elastic replanning: drift re-selection + mesh shrink ==")
        assert args.devices >= 9, "elastic check needs >= 9 devices"
        import dataclasses as _dc

        from repro import obs
        from repro.core import roofline
        from repro.core.bsr import rmat_matrix
        from repro.runtime.faultinject import (DeviceLoss,
                                               record_straggler_drift)
        from repro.runtime.replan import ElasticReplanner, ReplanConfig

        # -- part 1: straggler drift trips a re-fit that flips auto_select
        a = DistDense.from_global(
            rng.standard_normal((64, 64)).astype(np.float32), 2)
        b = DistDense.from_global(
            rng.standard_normal((64, 32)).astype(np.float32), 2)
        mesh2 = make_grid_mesh(2)
        # nominal machine: optimistically fast interconnect -> a
        # bandwidth-hungry schedule wins at plan time
        base = _dc.replace(roofline.TPU_V5E, name="v5e-fastnet",
                           net_bw=roofline.TPU_V5E.net_bw * 100,
                           hop_latency=1e-9)
        obs.reset_all()
        obs.enable(clear=True, drift=True)   # the replanner reads drift
        api.set_drift_machine(base)
        try:
            p0 = api.plan_matmul(a, b, algorithm="auto", machine=base,
                                 mesh=mesh2)
            ref = np.asarray(a.data) @ np.asarray(b.data)
            check("elastic/nominal_result", p0(a, b), ref)
            # straggling network: measured steps 8x the prediction, on two
            # algorithm series so the machine re-fit is well conditioned
            p_alt = api.plan_matmul(a, b, algorithm="summa_bcast",
                                    mesh=mesh2)
            record_straggler_drift(p0, factor=8.0, n=4, machine=base)
            record_straggler_drift(p_alt, factor=8.0, n=4, machine=base)
            rp = ElasticReplanner(machine=base,
                                  config=ReplanConfig(drift_ratio=2.0))
            trips = rp.should_replan()
            check_flag(f"elastic/drift_trips ({sorted(trips)})",
                       bool(trips))
            res = rp.replan(a, b, mesh=mesh2)
            check_flag(
                f"elastic/reselect_flips ({p0.algorithm.name} -> "
                f"{res.algorithm}, evicted={res.evicted})",
                res.algorithm != p0.algorithm.name and res.evicted > 0)
            check("elastic/replanned_result", res.plan(a, b), ref)

            # -- part 2: device loss -> grid shrink -> rebuilt steal plan
            a_d = rmat_matrix(scale=6, edgefactor=8, seed=args.seed)
            bx = rng.standard_normal((64, 48)).astype(np.float32)
            a3 = DistBSR.from_dense(a_d, g=3, block_size=4)
            b3 = DistDense.for_rhs(jnp.asarray(bx), a3)
            mesh3 = make_grid_mesh(3)
            p3 = api.plan_matmul(a3, b3, algorithm="steal3d", mesh=mesh3,
                                 validate="fast")
            want = a_d @ bx
            check("elastic/preloss_result", p3(a3, b3), want)
            loss = DeviceLoss(9, 5, seed=args.seed)
            rec = rp.recover_from_loss(a3, b3, loss.survivors(),
                                       mesh=mesh2)
            check_flag(
                f"elastic/shrink_3x3_to_2x2 (survivors="
                f"{loss.survivors()}, g={rec.g}, evicted={rec.evicted})",
                rec.g == 2 and rec.evicted > 0)
            check("elastic/recovered_result", rec.plan(rec.a, rec.b), want)
            snap = obs.registry().snapshot()
            wanted_metrics = ("replan.triggered", "replan.refits",
                              "replan.plans_evicted", "replan.recoveries")
            missing = [k for k in wanted_metrics if k not in snap]
            check_flag(f"elastic/metrics_recorded (missing={missing})",
                       not missing)
        finally:
            api.set_drift_machine(None)
            obs.disable()

    if args.check in ("all", "train_parallel"):
        print("== data/tensor-parallel train step equivalence ==")
        from repro.launch.train import selftest_parallel_equivalence
        ok = selftest_parallel_equivalence(args.devices)
        print(f"  [{'ok' if ok else 'FAIL'}] train/dp_tp_equivalence")
        if not ok:
            failures.append("train_parallel")

    if failures:
        print(f"SELFTEST FAILED: {failures}")
        return 1
    print("SELFTEST PASSED")
    return 0


if __name__ == "__main__":
    sys.exit(main())
