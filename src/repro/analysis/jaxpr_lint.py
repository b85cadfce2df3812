"""Jaxpr lint: structural rules over a plan's traced executable.

The schedule bodies are scanned/flat jaxprs whose communication structure
is fully static, so three properties can be proven by walking the trace
instead of measured at runtime:

* ``jaxpr.scan-hot-loop`` — scanned schedule steps stay sort/scatter-free
  (coverage augmentation and B-densification are hoisted to plan time;
  a sort or scatter inside the ring step is the hot-loop bloat PR 2
  eliminated creeping back in).
* ``jaxpr.collective-count`` — the number of collective message groups in
  the trace equals the cost model's message count (``n_msgs`` for
  steal3d, ``msgs_per_step``/wire-derived otherwise).  This catches
  cost-model/code drift statically, before ``fit_machine`` fits
  constants against a miscounted model.  Skipped at g == 1, where the
  forward and backward ring permutations collapse to the same
  ``[(0, 0)]`` and message groups alias.
* ``jaxpr.overlap-carry`` — the double-buffered two-slot carry discipline:
  in an overlap scan body, step t+1's transfer is issued before step t's
  accumulate, and no collective's in-flight output reaches a compute op
  inside the same body (computes must consume the *carried* slot).

The walk primitives (:func:`subjaxprs`, :func:`iter_eqns`,
:func:`scan_eqns`) are the single shared copy of the helpers that used to
be duplicated across ``tests/test_api.py`` / ``test_wire.py`` /
``test_overlap.py``.
"""
from __future__ import annotations

from typing import Iterator, List, Optional, Tuple

from .findings import Finding

#: primitives that ship bytes between devices (jaxpr primitive names)
COLLECTIVE_PRIMS = ("ppermute", "psum", "all_gather", "all_to_all",
                    "reduce_scatter")

#: primitives (by substring) banned inside scanned schedule steps
HOT_LOOP_BANNED = ("sort", "scatter")

#: primitives that do real math on tile payloads — "compute" for the
#: happens-before race check, and group breakers for message counting
COMPUTE_PRIMS = ("dot_general", "pallas_call", "conv_general_dilated")


# ---------------------------------------------------------------------------
# walk primitives (shared with the test suite)
# ---------------------------------------------------------------------------
def subjaxprs(v) -> Iterator:
    """Yield every Jaxpr reachable from an eqn-param value."""
    from jax.extend import core as jcore
    if isinstance(v, jcore.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, jcore.Jaxpr):
        yield v
    elif isinstance(v, (list, tuple)):
        for x in v:
            yield from subjaxprs(x)


def iter_eqns(jaxpr) -> Iterator:
    """Depth-first over all eqns, recursing through sub-jaxpr params."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in subjaxprs(v):
                yield from iter_eqns(sub)


def scan_eqns(jaxpr) -> List:
    """All ``scan`` eqns anywhere in the jaxpr."""
    return [e for e in iter_eqns(jaxpr) if e.primitive.name == "scan"]


def scan_body_primitives(jaxpr) -> set:
    """Primitive names appearing inside any scanned body."""
    prims = set()
    for eqn in scan_eqns(jaxpr):
        for sub in iter_eqns(eqn.params["jaxpr"].jaxpr):
            prims.add(sub.primitive.name)
    return prims


def trace_plan(plan, a, b):
    """Trace exactly what ``plan(a, b)`` executes and return the jaxpr.

    Uses ``plan._operands`` so the linted trace is the executed trace
    (packed wire trees, steal3d aux, sparse pair lists included).  The
    plan's trace counter is restored afterwards — linting must not
    perturb the retrace-count invariants the test suite asserts.
    """
    import jax
    from repro.core import api as _api
    a_h, b_h = _api._coerce_pair(a, b, g=plan.geom.g,
                                 allow_pad=plan._allow_pad)
    operands = plan._operands(a_h, b_h)
    traces0 = plan.traces
    try:
        closed = jax.make_jaxpr(lambda *xs: plan._exec(*xs))(*operands)
    finally:
        plan.traces = traces0
    return closed.jaxpr


# ---------------------------------------------------------------------------
# rule: jaxpr.scan-hot-loop
# ---------------------------------------------------------------------------
def check_hot_loop(jaxpr, impl: Optional[str] = None) -> List[Finding]:
    if impl in (None, "auto"):
        from repro.kernels.ops import default_impl
        impl = default_impl()
    if impl == "ref":
        # the reference (numpy-style) kernel accumulates via scatter-add
        # by design; the gather-only contract binds the pallas paths
        return []
    offenders = sorted(
        p for p in scan_body_primitives(jaxpr)
        if any(bad in p for bad in HOT_LOOP_BANNED))
    if not offenders:
        return []
    return [Finding(
        "jaxpr.scan-hot-loop",
        f"scanned schedule step contains {offenders}: coverage "
        "augmentation / densification must be hoisted to plan time "
        "(pre-augmented tiles, plan-built consume maps), not re-done "
        "every ring step")]


# ---------------------------------------------------------------------------
# rule: jaxpr.collective-count
# ---------------------------------------------------------------------------
def _is_compute(name: str) -> bool:
    return name in COMPUTE_PRIMS or any(b in name for b in HOT_LOOP_BANNED)


def _collective_key(eqn) -> tuple:
    params = eqn.params
    key = tuple(sorted(
        (k, str(params[k]))
        for k in ("axis_name", "axes", "perm", "axis_index_groups")
        if k in params))
    return (eqn.primitive.name, key)


def count_message_groups(jaxpr) -> int:
    """Count collective *message groups* in trace order.

    A message group is one logical shipment: a float-payload collective
    plus any immediately following integer-payload collectives with the
    same (primitive, axis/perm) — the blocks/rows/cols legs of one
    tree-ppermute'd sparse tile are one message, while two independent
    float payloads (say B's tile and the riding-home C partial) are two
    even when they share a ring.  Groups inside a ``scan`` body count
    once per iteration (times the scan length); compute ops, control
    flow and scan boundaries end the current group.
    """
    events: List[Optional[Tuple[tuple, bool, int]]] = []

    def walk(jx, mult: int) -> None:
        for eqn in jx.eqns:
            name = eqn.primitive.name
            if name == "scan":
                events.append(None)
                walk(eqn.params["jaxpr"].jaxpr,
                     mult * int(eqn.params["length"]))
                events.append(None)
                continue
            if name == "pvary":     # axis-metadata no-op, not a message
                continue
            if name in COLLECTIVE_PRIMS:
                int_payload = all(
                    getattr(v.aval.dtype, "kind", "f") in "iub"
                    for v in eqn.outvars)
                events.append((_collective_key(eqn), int_payload, mult))
                continue
            subs = [s for v in eqn.params.values() for s in subjaxprs(v)]
            if _is_compute(name) or name in ("while", "cond"):
                events.append(None)
                for s in subs:
                    walk(s, mult)
                    events.append(None)
            elif subs:              # pjit/closed_call etc: transparent
                for s in subs:
                    walk(s, mult)
    walk(jaxpr, 1)

    total = 0
    cur_key = None
    for ev in events:
        if ev is None:
            cur_key = None
            continue
        key, int_payload, mult = ev
        if key == cur_key and int_payload:
            continue                # metadata rider on the current group
        total += mult
        cur_key = key
    return total


def check_collective_count(plan, jaxpr) -> List[Finding]:
    if plan.geom.g < 2:
        return []    # degenerate ring perms alias; counted in selftest
    from repro.core import roofline as _roofline
    from repro.core.api import _time_breakdown
    cm = plan.cost_model()
    expected = int(round(_time_breakdown(
        cm, plan.algorithm, _roofline.TPU_V5E, plan.overlap)["msgs"]))
    got = count_message_groups(jaxpr)
    if got == expected:
        return []
    return [Finding(
        "jaxpr.collective-count",
        f"trace has {got} collective message group(s) but the cost model "
        f"charges {expected} (n_msgs/msgs_per_step); the model and the "
        "schedule body have drifted — fix whichever is wrong before "
        "fit_machine calibrates against the miscount",
        subject=f"{plan.algorithm.name}/{plan.wire}")]


# ---------------------------------------------------------------------------
# rule: jaxpr.overlap-carry
# ---------------------------------------------------------------------------
def check_overlap_carry(plan, jaxpr) -> List[Finding]:
    if not plan.geom.overlap:
        return []
    from jax.extend import core as jcore
    findings = []
    for scan in scan_eqns(jaxpr):
        body = scan.params["jaxpr"].jaxpr
        first_coll = first_comp = None
        tainted = set()
        for idx, eqn in enumerate(body.eqns):
            name = eqn.primitive.name
            invars = [v for v in eqn.invars if isinstance(v, jcore.Var)]
            if name in COLLECTIVE_PRIMS:
                if first_coll is None:
                    first_coll = idx
                tainted.update(eqn.outvars)
            elif _is_compute(name):
                if first_comp is None:
                    first_comp = idx
                hot = [str(v) for v in invars if v in tainted]
                if hot:
                    findings.append(Finding(
                        "jaxpr.overlap-carry",
                        f"compute op {name!r} consumes in-flight transfer "
                        f"output {hot} inside the scan body that issued "
                        "it — the double-buffered contract is compute on "
                        "the carried slot while the next slot's transfer "
                        "flies; carry the fresh buffer and consume it "
                        "next step",
                        subject=f"{plan.algorithm.name}/overlap"))
            elif any(v in tainted for v in invars):
                tainted.update(eqn.outvars)
        if first_coll is not None and first_comp is not None \
                and first_comp < first_coll:
            findings.append(Finding(
                "jaxpr.overlap-carry",
                "overlap scan body accumulates before issuing step t+1's "
                "transfer (first compute eqn precedes first collective) — "
                "the transfer can no longer fly under this step's "
                "compute; issue the collectives first",
                subject=f"{plan.algorithm.name}/overlap"))
    return findings


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------
RULES = (
    ("jaxpr.scan-hot-loop",
     "scanned schedule steps contain no sort/scatter primitives"),
    ("jaxpr.collective-count",
     "collective message groups in the trace == cost model msgs (g >= 2)"),
    ("jaxpr.overlap-carry",
     "overlap bodies issue transfers first and never compute on "
     "in-flight buffers"),
)


def lint_plan(plan, a=None, b=None, *, jaxpr=None) -> List[Finding]:
    """Run every jaxpr rule over the plan's executed trace.

    Pass the plan's operands (handles or raw values) so the trace covers
    the real operand trees, or a pre-traced ``jaxpr``.
    """
    if jaxpr is None:
        if a is None or b is None:
            raise ValueError(
                "lint_plan needs the plan's operands (or jaxpr=) to trace "
                "the executable")
        jaxpr = trace_plan(plan, a, b)
    return (check_hot_loop(jaxpr, impl=plan.geom.impl)
            + check_collective_count(plan, jaxpr)
            + check_overlap_carry(plan, jaxpr))
