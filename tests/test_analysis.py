"""Static analysis tests: schedule verifier, jaxpr lint, source rules.

Three layers, matching ``src/repro/analysis``:

* **clean-plan contract** — every healthy plan across the dispatch
  matrix (algorithm x output x wire x overlap) produces *zero* findings
  from both the schedule checker and the jaxpr lint;
* **mutation tests** — each seeded violation class (corrupted ppermute
  permutation, dropped/duplicated steal3d accumulation item, rolled
  packed-wire consume map, corrupted sparse pair list, overlap bodies
  that consume in-flight buffers or issue transfers late) is flagged
  with its named rule id and an actionable message.  The
  ``jaxpr.collective-count`` drift rule needs g >= 2 and is mutated in
  ``selftest --check analysis`` (it rides tier-1 via
  ``tools/run_tier1.sh``);
* **plumbing** — ``plan_matmul(validate=...)`` modes, memoization and
  the never-cache-a-failing-plan rule; ``validate_assignment`` fail-fast
  on injected :class:`Assignment3D`; the ``source_rules`` registry
  (rule ids, ``--json`` / ``--list-rules``, per-line waiver pragmas).

Single-device (g=1) like the rest of the suite; multi-device coverage
rides ``selftest --check analysis`` on 4 fake devices.
"""
import dataclasses
import json

import numpy as np
import pytest

import jax.numpy as jnp
from jax import lax

from repro import analysis
from repro.analysis import source_rules
from repro.analysis.jaxpr_lint import (check_collective_count,
                                       check_hot_loop, trace_plan)
from repro.core import api, schedules
from repro.core import steal3d  # analysis: allow(source.import.repro.core.steal3d)
from repro.core.api import Algorithm, DistBSR, DistDense, plan_matmul
from repro.core.bsr import random_sparse
from repro.core.schedule import assign_3d_lpt

G = 1  # the main pytest process owns a single CPU device


@pytest.fixture
def operands():
    a_d = random_sparse(16, 16, 0.3, seed=0)
    b = np.random.default_rng(0).standard_normal((16, 8)).astype(np.float32)
    a_h = DistBSR.from_dense(a_d, g=G, block_size=4)
    b_h = DistDense.for_rhs(jnp.asarray(b), a_h)
    b_sph = DistBSR.from_dense(random_sparse(16, 16, 0.25, seed=1), g=G,
                               block_size=4)
    return a_h, b_h, b_sph


def _rules_of(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# Clean-plan contract: zero findings across the dispatch matrix
# ---------------------------------------------------------------------------
_DENSE_ALGS = ("ring_c", "ring_a", "ring_c_bidir", "summa_ag",
               "summa_bcast", "steal3d")
_SPARSE_OUT_ALGS = ("ring_c", "summa_ag", "summa_bcast")
_SPGEMM_ALGS = ("ring_c", "ring_a", "summa_ag", "summa_bcast", "steal3d")

_MATRIX = (
    [(alg, "spmm", "dense", wire, ov)
     for alg in _DENSE_ALGS
     for wire in ("padded", "packed")
     for ov in ("off", "on")]
    + [(alg, "spgemm", "sparse", wire, "off")
       for alg in _SPARSE_OUT_ALGS
       for wire in ("padded", "packed")]
    + [(alg, "spgemm", "dense", "padded", "off") for alg in _SPGEMM_ALGS]
)


@pytest.mark.parametrize(
    "alg,kind,output,wire,overlap", _MATRIX,
    ids=[f"{a}-{k}-{o}-{w}-ov_{v}" for a, k, o, w, v in _MATRIX])
def test_healthy_plans_prove_clean(operands, alg, kind, output, wire,
                                   overlap):
    a_h, b_h, b_sph = operands
    rhs = b_h if kind == "spmm" else b_sph
    plan = plan_matmul(a_h, rhs, algorithm=alg, impl="ref", output=output,
                       wire=wire, overlap=overlap)
    findings = analysis.check_plan(plan, a_h, rhs) \
        + analysis.lint_plan(plan, a_h, rhs)
    assert not findings, "\n".join(str(f) for f in findings)


def test_registry_rule_ids_unique_and_documented():
    rules = analysis.all_rules()
    ids = [r for r, _ in rules]
    assert len(ids) == len(set(ids))
    for prefix in ("schedule.", "jaxpr.", "source."):
        assert any(r.startswith(prefix) for r in ids), prefix
    assert all(desc for _, desc in rules)


def test_finding_and_error_formatting():
    f = analysis.Finding("x.rule", "broken thing", subject="ring_c/step 2")
    assert str(f) == "x.rule [ring_c/step 2]: broken thing"
    err = analysis.PlanValidationError([f])
    assert "x.rule" in str(err) and "1 finding" in str(err)
    assert err.findings == [f]
    assert isinstance(err, ValueError)


# ---------------------------------------------------------------------------
# Mutation tests: every seeded violation class is flagged by rule id
# ---------------------------------------------------------------------------
def test_mutation_invalid_ppermute_perm(operands, monkeypatch):
    """A non-bijective ring permutation (would deadlock the ppermute) is
    named by schedule.ppermute-bijection."""
    a_h, b_h, _ = operands
    plan = plan_matmul(a_h, b_h, algorithm="ring_c", impl="ref",
                       cache=False)
    monkeypatch.setattr(schedules, "_ring_perm", lambda g, sign=1: ((0, 1),))
    findings = analysis.check_plan(plan, a_h, b_h)
    assert "schedule.ppermute-bijection" in _rules_of(findings)
    msg = str([f for f in findings
               if f.rule == "schedule.ppermute-bijection"][0])
    assert "bijection" in msg or "deadlock" in msg or "device" in msg


def _copied_aux(sp):
    return {k: np.asarray(v).copy() for k, v in sp.aux.items()}


def test_mutation_steal_dropped_accumulation(operands):
    """Blanking one real (A, B) pair drops its (i, k, j) block product:
    schedule.steal-exactly-once."""
    a_h, b_h, _ = operands
    plan = plan_matmul(a_h, b_h, algorithm="steal3d", impl="ref",
                       cache=False)
    sp = plan.steal
    try:
        aux = _copied_aux(sp)
        pa = aux["pa"]
        inert = pa.reshape(-1).max()  # the zero-block sentinel slot
        pa[tuple(np.argwhere(pa != inert)[0])] = inert
        plan.steal = dataclasses.replace(sp, aux=aux)
        findings = analysis.check_plan(plan, a_h, b_h)
        assert "schedule.steal-exactly-once" in _rules_of(findings)
    finally:
        plan.steal = sp
    assert not analysis.check_plan(plan, a_h, b_h)


def test_mutation_steal_duplicated_accumulation(operands):
    """Copying a real pair onto an inert slot of the same device double-
    counts its block product: schedule.steal-exactly-once."""
    a_h, b_h, _ = operands
    plan = plan_matmul(a_h, b_h, algorithm="steal3d", impl="ref",
                       cache=False)
    sp = plan.steal
    try:
        aux = _copied_aux(sp)
        pa, pb, ps = aux["pa"], aux["pb"], aux["ps"]
        inert = pa.reshape(-1).max()
        r0 = tuple(np.argwhere(pa != inert)[0])
        same_dev = [tuple(i) for i in np.argwhere(pa == inert)
                    if tuple(i[:2]) == r0[:2]]
        i0 = same_dev[0]
        pa[i0], pb[i0], ps[i0] = pa[r0], pb[r0], ps[r0]
        plan.steal = dataclasses.replace(sp, aux=aux)
        findings = analysis.check_plan(plan, a_h, b_h)
        assert "schedule.steal-exactly-once" in _rules_of(findings)
    finally:
        plan.steal = sp
    assert not analysis.check_plan(plan, a_h, b_h)


def test_mutation_broken_consume_map(operands):
    """Rolling the packed-wire gidx consume map desynchronizes it from
    the pack layout: schedule.wire-contract."""
    a_h, b_h, _ = operands
    plan = plan_matmul(a_h, b_h, algorithm="ring_c", impl="ref",
                       wire="packed", cache=False)
    good = np.asarray(plan._aux["a_gidx"])
    try:
        plan._aux["a_gidx"] = np.roll(good, 1, axis=-1)
        findings = analysis.check_plan(plan, a_h, b_h)
        assert "schedule.wire-contract" in _rules_of(findings)
    finally:
        plan._aux["a_gidx"] = good
    assert not analysis.check_plan(plan, a_h, b_h)


def test_mutation_corrupt_sparse_pair_list(operands):
    """Pointing a sparse-output pair at the zero slot drops a real
    (i, k, j) contribution: schedule.sparse-pairs-exactly-once."""
    a_h, _, b_sph = operands
    plan = plan_matmul(a_h, b_sph, algorithm="ring_c", impl="ref",
                       output="sparse", wire="padded", cache=False)
    good = plan._pairs["pb"]
    pb = np.asarray(good).copy()
    zero_slot = int(np.asarray(b_sph.grid_structure().zero_slot)[0, 0])
    try:
        pb[0, 0, 0, 0] = zero_slot  # first real pair now consumes zeros
        plan._pairs["pb"] = pb
        findings = analysis.check_plan(plan, a_h, b_sph)
        assert "schedule.sparse-pairs-exactly-once" in _rules_of(findings)
    finally:
        plan._pairs["pb"] = good
    assert not analysis.check_plan(plan, a_h, b_sph)


def _overlap_taint_body(a, b, geom):
    """Broken overlap: computes on the in-flight ppermute output."""
    bb = schedules._densify_b(b, geom)
    acc0 = schedules._pvary(jnp.zeros((geom.tm, geom.tn), geom.out_dtype), geom)

    def step(carry, _):
        b_t, acc = carry
        b_n = schedules._tree_ppermute(b_t, geom.axr, geom.g)
        acc = acc + schedules._local_mm(a, b_n, geom)
        return (b_n, acc), None

    (_, acc), _ = lax.scan(step, (bb, acc0), None, length=geom.g)
    return acc


def _overlap_late_issue_body(a, b, geom):
    """Broken overlap: accumulates before issuing step t+1's transfer."""
    bb = schedules._densify_b(b, geom)
    acc0 = schedules._pvary(jnp.zeros((geom.tm, geom.tn), geom.out_dtype), geom)

    def step(carry, _):
        b_t, acc = carry
        acc = acc + schedules._local_mm(a, b_t, geom)
        b_n = schedules._tree_ppermute(b_t, geom.axr, geom.g)
        return (b_n, acc), None

    (_, acc), _ = lax.scan(step, (bb, acc0), None, length=geom.g)
    return acc


@pytest.mark.parametrize("body", [_overlap_taint_body,
                                  _overlap_late_issue_body],
                         ids=["inflight-consume", "late-issue"])
def test_mutation_reordered_overlap_carry(operands, body):
    a_h, b_h, _ = operands
    name = "bad_overlap_body"
    api.REGISTRY.register(Algorithm(name=name, body=body, msgs_per_step=1))
    try:
        plan = plan_matmul(a_h, b_h, algorithm=name, impl="ref",
                           overlap="on", cache=False)
        findings = analysis.lint_plan(plan, a_h, b_h)
        assert "jaxpr.overlap-carry" in _rules_of(findings)
        msg = str([f for f in findings
                   if f.rule == "jaxpr.overlap-carry"][0])
        assert "carr" in msg or "transfer" in msg  # actionable, not bare
    finally:
        api.REGISTRY.unregister(name)


def test_hot_loop_rule_binds_pallas_paths_only(operands):
    """The reference kernel accumulates via scatter-add by design, so the
    gather-only contract is exempt under impl='ref' but the same trace is
    flagged when a pallas/interpret impl claims it."""
    a_h, b_h, _ = operands
    plan = plan_matmul(a_h, b_h, algorithm="ring_c", impl="ref",
                       cache=False)
    jaxpr = trace_plan(plan, a_h, b_h)
    assert check_hot_loop(jaxpr, impl="ref") == []
    findings = check_hot_loop(jaxpr, impl="interpret")
    assert _rules_of(findings) == ["jaxpr.scan-hot-loop"]


def test_collective_count_skips_degenerate_grid(operands):
    """At g == 1 the ring perms alias, so the n_msgs drift rule abstains
    (the real mutation runs at g=2 in selftest --check analysis)."""
    a_h, b_h, _ = operands
    plan = plan_matmul(a_h, b_h, algorithm="ring_c", impl="ref",
                       cache=False)
    jaxpr = trace_plan(plan, a_h, b_h)
    assert check_collective_count(plan, jaxpr) == []


# ---------------------------------------------------------------------------
# plan_matmul(validate=...) plumbing
# ---------------------------------------------------------------------------
def test_validate_modes_pass_and_memoize(operands):
    a_h, b_h, _ = operands
    plan = plan_matmul(a_h, b_h, algorithm="ring_c", impl="ref",
                       cache=False, validate="fast")
    assert "fast" in plan._validated and "full" not in plan._validated
    plan.validate("full", a_h, b_h)
    assert {"fast", "full"} <= plan._validated
    # re-validating a verified plan is a no-op (memoized verdict)
    plan.validate("full", a_h, b_h)
    plan2 = plan_matmul(a_h, b_h, algorithm="steal3d", impl="ref",
                        cache=False, validate="full")
    assert {"fast", "full"} <= plan2._validated


def test_validate_off_and_bad_mode(operands):
    a_h, b_h, _ = operands
    plan = plan_matmul(a_h, b_h, algorithm="ring_c", impl="ref",
                       cache=False)
    assert plan._validated == set()
    with pytest.raises(ValueError, match="validate"):
        plan_matmul(a_h, b_h, algorithm="ring_c", impl="ref",
                    validate="paranoid")


def test_validate_failing_plan_raises_and_is_not_cached(operands,
                                                        monkeypatch):
    a_h, b_h, _ = operands
    api.clear_plan_cache()
    monkeypatch.setattr(schedules, "_ring_perm", lambda g, sign=1: ((0, 1),))
    with pytest.raises(analysis.PlanValidationError) as ei:
        plan_matmul(a_h, b_h, algorithm="ring_c", impl="ref",
                    validate="fast")
    assert "schedule.ppermute-bijection" in str(ei.value)
    assert api.plan_cache_size() == 0  # a failing plan never enters
    monkeypatch.undo()
    plan = plan_matmul(a_h, b_h, algorithm="ring_c", impl="ref",
                       validate="fast")
    assert api.plan_cache_size() == 1
    # the cache-hit path re-validates (memoized) instead of skipping
    plan_b = plan_matmul(a_h, b_h, algorithm="ring_c", impl="ref",
                         validate="full")
    assert plan_b is plan and "full" in plan._validated


# ---------------------------------------------------------------------------
# steal3d: fail-fast Assignment3D validation + injection
# ---------------------------------------------------------------------------
def _lpt_fixture(g=2, seed=3):
    rng = np.random.default_rng(seed)
    cost_ik = rng.integers(1, 20, size=(g, g)).astype(np.float64)
    flops = np.broadcast_to(cost_ik[:, :, None], (g, g, g))
    return cost_ik, assign_3d_lpt(flops, g)


def test_validate_assignment_accepts_lpt_result():
    cost_ik, asg = _lpt_fixture()
    assert steal3d.validate_assignment(asg, 2) is asg
    assert steal3d.validate_assignment(asg, 2, cost_ik=cost_ik) is asg


def test_validate_assignment_rejects_bad_shape_and_dtype():
    _, asg = _lpt_fixture()
    with pytest.raises(ValueError, match="shape"):
        steal3d.validate_assignment(
            dataclasses.replace(asg, dev=np.zeros((2, 2), np.int64)), 2)
    with pytest.raises(ValueError, match="integer"):
        steal3d.validate_assignment(
            dataclasses.replace(asg, dev=asg.dev.astype(np.float64)), 2)


def test_validate_assignment_rejects_out_of_range_device():
    _, asg = _lpt_fixture()
    dev = asg.dev.copy()
    dev[0, 0, 0] = 4  # g*g for g=2
    with pytest.raises(ValueError, match="outside"):
        steal3d.validate_assignment(dataclasses.replace(asg, dev=dev), 2)


def test_validate_assignment_rejects_locality_violation():
    _, asg = _lpt_fixture()
    dev = asg.dev.copy()
    dev[0, 0, 1] = 2  # device (1, 0): neither row 0 nor column 1
    with pytest.raises(ValueError, match="locality"):
        steal3d.validate_assignment(dataclasses.replace(asg, dev=dev), 2)


def test_validate_assignment_rejects_makespan_regressions():
    cost_ik, asg = _lpt_fixture()
    with pytest.raises(ValueError, match="makespan"):
        steal3d.validate_assignment(
            dataclasses.replace(asg, makespan=asg.owner_makespan * 2), 2)
    # recorded fields fine, but realized loads (all of row 0 piled on
    # device (0, 0)) exceed owner-computes once recomputed from cost_ik
    g = 2
    owner = assign_3d_lpt(np.broadcast_to(cost_ik[:, :, None], (g, g, g)),
                          g, locality="none")
    dev = owner.dev.copy()
    dev[0, :, :] = 0
    with pytest.raises(ValueError, match="realized makespan"):
        steal3d.validate_assignment(
            dataclasses.replace(owner, dev=dev), g, cost_ik=cost_ik)


def test_build_steal_plan_assignment_injection(operands):
    a_h, b_h, _ = operands
    plan = plan_matmul(a_h, b_h, algorithm="steal3d", impl="ref",
                       cache=False)
    asg = plan.steal.assignment
    sp2 = steal3d.build_steal_plan(a_h, b_h, plan.geom, assignment=asg)
    assert sp2.assignment is asg
    assert np.array_equal(np.asarray(sp2.aux["pa"]),
                          np.asarray(plan.steal.aux["pa"]))
    bad = dataclasses.replace(asg, dev=np.zeros((2, 2, 2), np.int64))
    with pytest.raises(ValueError, match="shape"):
        steal3d.build_steal_plan(a_h, b_h, plan.geom, assignment=bad)


# ---------------------------------------------------------------------------
# source_rules: registry, CLI flags, waiver pragmas
# ---------------------------------------------------------------------------
def test_source_rule_registry_covers_legacy_families():
    ids = [r.id for r in source_rules.iter_rules()]
    assert len(ids) == len(set(ids))
    assert len(ids) == len(source_rules.FORBIDDEN_MODULES) + 3
    for mod in source_rules.FORBIDDEN_MODULES:
        assert f"source.import.{mod}" in ids
    assert "source.xla-flags-write" in ids
    assert "source.perf-counter-discipline" in ids
    assert "source.assignment3d-construction" in ids


def test_source_rules_list_rules_flag(capsys):
    assert source_rules.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in source_rules.iter_rules():
        assert rule.id in out
    assert source_rules.main(["--list-rules", "--json"]) == 0
    listed = json.loads(capsys.readouterr().out)
    assert {e["rule"] for e in listed} \
        == {r.id for r in source_rules.iter_rules()}


def test_source_rules_json_output_and_waiver(tmp_path, capsys):
    (tmp_path / "examples").mkdir()
    bad = tmp_path / "examples" / "bad.py"
    bad.write_text("from repro.core.spmm import spmm\n")
    assert source_rules.main(["--json", str(tmp_path)]) == 1
    report = json.loads(capsys.readouterr().out)
    assert not report["ok"]
    assert report["violations"][0]["rule"] == "source.import.repro.core.spmm"
    assert report["violations"][0]["line"] == 1
    # per-line waiver pragma suppresses exactly that rule on that line
    bad.write_text("from repro.core.spmm import spmm"
                   "  # analysis: allow(source.import.repro.core.spmm)\n")
    assert source_rules.main(["--json", str(tmp_path)]) == 0
    assert json.loads(capsys.readouterr().out)["ok"]


def test_source_rules_waiver_is_rule_specific(tmp_path):
    (tmp_path / "examples").mkdir()
    bad = tmp_path / "examples" / "bad.py"
    bad.write_text("from repro.core.spmm import spmm"
                   "  # analysis: allow(source.xla-flags-write)\n")
    found = source_rules.violations(str(tmp_path))
    assert len(found) == 1 and "spmm" in found[0]
