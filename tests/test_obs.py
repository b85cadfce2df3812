"""Observability subsystem tests (``repro.obs``).

Covers the contracts the obs layer makes:

* **Disabled tracing is a no-op**: ``span()`` returns one shared inert
  object, no events accumulate, and instrumented plan calls take the
  early-return path.  Importing ``repro.obs`` imports no jax.
* **Spans nest and are thread-safe**: interval containment and recorded
  depth reconstruct the stack; concurrent recorders lose no events.
* **Spans are on the profiler's clock**: a span taken in a
  ``jax.profiler`` session lies on the trace's host plane under its name,
  where the buffered event says it does.
* **Spans never change what the program does**: with drift off, a traced
  plan call neither blocks nor runs the cost model.
* **Chrome-trace schema**: ``export_trace`` round-trips through JSON with
  every event carrying ``ph``/``ts``/``dur``/``name``/``pid``/``tid``,
  and ``validate_trace`` catches violations.
* **Registry semantics**: labeled series identity, snapshot rendering,
  reset-keeps-registrations, pull-time callbacks, kind conflicts.
* **Drift math**: ratio is geomean(measured/predicted), rmse is exact;
  ``fit_from_registry`` recovers known machine constants from synthetic
  drift records.
* **Instrumented plan path**: traced ``plan_matmul`` + ``MatmulPlan``
  calls emit plan-build and per-multiply spans, record drift when drift
  recording is on, and the ``jax.named_scope`` wrapper adds zero
  retraces; the scope label survives into compiled HLO
  (``scope_op_counts``).  Sparse-output plans set the ``plan.real_pairs``,
  ``plan.pair_steps`` and ``plan.pair_grid_steps`` gauges to the symbolic
  product's counts.
* **Serving spans**: a ServeEngine run under tracing emits
  admission/prefill/decode-step spans.
* **check_api timing rule**: raw paired ``perf_counter`` reads without a
  blocking call are flagged outside the allowlisted modules.
"""
import glob
import importlib.util
import json
import math
import os
import pathlib
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import api, roofline
from repro.core.api import DistBSR, DistDense
from repro.core.bsr import random_sparse


@pytest.fixture(autouse=True)
def _clean_obs_state():
    """Tracing/drift state is process-global; leave it as we found it."""
    obs.disable()
    obs.clear_trace()
    obs.reset_drift()
    yield
    obs.disable()
    obs.clear_trace()
    obs.reset_drift()


def _g1_handles(m=32, seed=11):
    a_d = random_sparse(m, m, 0.2, seed=seed)
    b = np.random.default_rng(seed).standard_normal((m, 8)).astype(
        np.float32)
    a_h = DistBSR.from_dense(a_d, g=1, block_size=4)
    b_h = DistDense.for_rhs(jnp.asarray(b), a_h)
    return a_d, b, a_h, b_h


# ---------------------------------------------------------------------------
# tracing: disabled no-op, nesting, threads, export schema
# ---------------------------------------------------------------------------
def test_disabled_span_is_shared_noop():
    assert not obs.enabled()
    s1, s2 = obs.span("a", k=1), obs.span("b")
    assert s1 is s2                       # one shared inert object
    with s1 as sp:
        sp.note(extra="ignored")          # must not raise
    assert obs.events() == []


def test_spans_nest_with_containment_and_depth():
    obs.enable(clear=True)
    with obs.span("outer", phase="build"):
        with obs.span("inner"):
            pass
        with obs.span("inner"):
            pass
    obs.disable()
    evs = obs.events()
    assert [e["name"] for e in evs] == ["inner", "inner", "outer"]
    outer = evs[-1]
    assert outer["args"]["depth"] == 0 and outer["args"]["phase"] == "build"
    for inner in evs[:2]:
        assert inner["args"]["depth"] == 1
        # interval containment (what Perfetto uses to rebuild the stack)
        assert inner["ts"] >= outer["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3


def test_span_note_attaches_mid_span_attrs():
    obs.enable(clear=True)
    with obs.span("x", a=1) as sp:
        sp.note(b=2)
    obs.disable()
    (ev,) = obs.events()
    assert ev["args"]["a"] == 1 and ev["args"]["b"] == 2


def test_tracing_is_thread_safe():
    obs.enable(clear=True)
    n_threads, per_thread = 8, 50

    def work(i):
        for j in range(per_thread):
            with obs.span(f"t{i}", j=j):
                pass

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    obs.disable()
    evs = obs.events()
    assert len(evs) == n_threads * per_thread       # nothing lost
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], set()).add(e["tid"])
    # each thread's spans carry a single consistent tid (the OS may
    # recycle pthread ids across short-lived threads, so tids need not
    # be globally distinct)
    assert len(by_name) == n_threads
    assert all(len(v) == 1 for v in by_name.values())


def test_export_trace_roundtrips_valid_chrome_json(tmp_path):
    obs.enable(clear=True)
    with obs.span("s", tag="v"):
        with obs.span("marker", n=3):
            pass
    obs.disable()
    path = tmp_path / "trace.json"
    obs.export_trace(str(path))
    trace = json.loads(path.read_text())
    assert obs.validate_trace(trace) == []
    assert trace["displayTimeUnit"] == "ms"
    assert trace["otherData"]["dropped_events"] == 0
    for ev in trace["traceEvents"]:
        for k in obs.REQUIRED_EVENT_KEYS:
            assert k in ev


def test_validate_trace_flags_schema_violations():
    assert obs.validate_trace({}) == ["traceEvents missing or not a list"]
    bad = {"traceEvents": [{"ph": "X", "ts": "zero", "dur": 1.0,
                            "name": "x", "pid": 0}]}
    problems = obs.validate_trace(bad)
    assert any("missing key 'tid'" in p for p in problems)
    assert any("ts not numeric" in p for p in problems)


def test_obs_imports_without_jax():
    code = ("import sys\nimport repro.obs\n"
            "assert 'jax' not in sys.modules, sorted(sys.modules)\n")
    env = dict(os.environ, PYTHONPATH=str(pathlib.Path(__file__).resolve()
                                          .parents[1] / "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


def test_span_lies_on_the_profiler_host_plane_at_its_buffered_time(tmp_path):
    """The buffered event's ``ts``/``dur`` are on the clock of the profiler's
    own host events: ``ts * 1e3 - profile_start_time`` is where the span's
    annotation starts in the ``.xplane.pb``."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        obs.enable(clear=True)
        with obs.span("obs.test.outer"):
            with obs.span("obs.test.inner"):
                jnp.ones((64, 64)).sum().block_until_ready()
    finally:
        obs.disable()
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    profile = ProfileData.from_file(path)
    (start,) = [dict(p.stats)["profile_start_time"] for p in profile.planes
                if p.name == "Task Environment"]
    on_host = {ev.name: (ev.start_ns, ev.end_ns)
               for p in profile.planes if p.name.startswith("/host:")
               for line in p.lines for ev in line.events
               if ev.name.startswith("obs.test.")}
    buffered = {e["name"]: e for e in obs.events()}
    assert set(on_host) == set(buffered) == {"obs.test.outer",
                                             "obs.test.inner"}
    for name, (s, e) in on_host.items():
        ev = buffered[name]
        assert abs(ev["ts"] * 1e3 - start - s) < 1e6           # 1 ms, in ns
        assert abs((ev["ts"] + ev["dur"]) * 1e3 - start - e) < 1e6


def test_drift_switch_is_its_own_and_off_by_default():
    obs.enable()
    assert obs.enabled() and not obs.drift_enabled()
    obs.enable(drift=True)
    assert obs.drift_enabled()
    obs.disable()
    assert not obs.enabled() and not obs.drift_enabled()


def test_clear_trace_and_enable_clear():
    obs.enable(clear=True)
    with obs.span("a"):
        pass
    assert len(obs.events()) == 1
    obs.enable(clear=True)                 # re-enable clears the buffer
    assert obs.events() == []
    obs.disable()


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------
def test_registry_instrument_identity_and_labels():
    reg = obs.MetricsRegistry()
    c = reg.counter("hits", cache="plans")
    c.inc()
    c.inc(2.5)
    assert reg.counter("hits", cache="plans") is c       # stateless call site
    other = reg.counter("hits", cache="symbolic")
    assert other is not c and other.value == 0.0
    assert c.value == 3.5
    assert len(reg.series("hits")) == 2


def test_registry_snapshot_rendering():
    reg = obs.MetricsRegistry()
    reg.counter("n").inc(4)
    reg.gauge("level").set(0.5)
    h = reg.histogram("lat", path="decode")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["n"] == 4                           # unlabeled -> value
    assert snap["level"] == 0.5
    row = snap["lat"]["path=decode"]                # labeled -> {labels: ...}
    assert row["count"] == 4 and row["sum"] == 10.0
    assert row["mean"] == 2.5 and row["min"] == 1.0 and row["max"] == 4.0
    assert row["p50"] == 2.5


def test_registry_reset_keeps_registrations_and_callbacks():
    reg = obs.MetricsRegistry()
    c = reg.counter("n")
    c.inc(7)
    reg.register_callback("pull", lambda: {"x": 1})
    reg.reset()
    assert reg.counter("n") is c and c.value == 0.0      # same instrument
    assert reg.snapshot() == {"n": 0.0, "pull": {"x": 1}}


def test_registry_kind_conflict_raises():
    reg = obs.MetricsRegistry()
    reg.counter("m")
    with pytest.raises(TypeError, match="already registered as counter"):
        reg.gauge("m")


def test_histogram_percentiles_interpolate():
    h = obs.Histogram("h", {})
    for v in (10.0, 20.0, 30.0, 40.0):
        h.observe(v)
    assert h.percentile(0) == 10.0
    assert h.percentile(100) == 40.0
    assert h.percentile(50) == 25.0
    assert math.isnan(obs.Histogram("e", {}).percentile(50))


def test_default_registry_exposes_plan_caches_callback():
    snap = obs.registry().snapshot()
    assert "plan_caches" in snap
    assert set(snap["plan_caches"]) == {"plans", "symbolic", "density",
                                        "steal"}


def test_steal3d_planning_feeds_registry():
    reg = obs.registry()
    moved = reg.counter("steal3d.moved_tile_bytes")
    built = reg.counter("steal3d.plans_built", wire="padded")
    m0, b0 = moved.value, built.value
    a_d, b, a_h, b_h = _g1_handles(m=32, seed=13)
    plan = api.plan_matmul(a_h, b_h, algorithm="steal3d", impl="ref",
                           cache=False)
    np.testing.assert_allclose(np.asarray(plan(a_h, b_h)), a_d @ b,
                               rtol=0, atol=1e-4)
    assert built.value >= b0 + 1      # this build was counted
    assert moved.value >= m0          # bytes only ever accumulate


# ---------------------------------------------------------------------------
# plan-cache counters window (cache_stats(reset=True))
# ---------------------------------------------------------------------------
def test_cache_stats_reset_windows_counters():
    a_d, b, a_h, b_h = _g1_handles()
    api.clear_plan_cache()
    api.cache_stats(reset=True)                    # open a fresh window
    api.plan_matmul(a_h, b_h, algorithm="ring_c", impl="ref")
    api.plan_matmul(a_h, b_h, algorithm="ring_c", impl="ref")   # hit
    stats = api.cache_stats(reset=True)            # read + close window
    assert stats["plans"]["misses"] >= 1 and stats["plans"]["hits"] >= 1
    after = api.cache_stats()
    assert after["plans"]["hits"] == 0 and after["plans"]["misses"] == 0
    assert after["plans"]["size"] >= 1             # entries survive the reset


# ---------------------------------------------------------------------------
# drift: math, report keys, fit-from-registry
# ---------------------------------------------------------------------------
def test_drift_ratio_and_rmse_exact():
    obs.record_drift("algx", "padded", "off", predicted_s=1.0,
                     measured_s=2.0)
    obs.record_drift("algx", "padded", "off", predicted_s=1.0,
                     measured_s=8.0)
    report = obs.drift_report()
    d = report["algx/padded/off"]
    assert d["n"] == 2
    assert d["ratio"] == pytest.approx(4.0)        # geomean(2, 8)
    assert d["rmse_s"] == pytest.approx(5.0)       # sqrt((1 + 49)/2)
    assert d["predicted_mean_s"] == pytest.approx(1.0)
    assert d["measured_mean_s"] == pytest.approx(5.0)


def test_drift_series_keyed_by_alg_wire_overlap():
    obs.record_drift("a1", "padded", "off", 1.0, 1.0)
    obs.record_drift("a1", "packed", "off", 1.0, 1.0)
    obs.record_drift("a2", "padded", "auto", 1.0, 1.0)
    assert set(obs.drift_report()) == {"a1/padded/off", "a1/packed/off",
                                       "a2/padded/auto"}
    assert len(obs.drift_records()) == 3
    obs.reset_drift()
    assert obs.drift_report() == {} and obs.drift_records() == []


def _load_fit_machine():
    path = pathlib.Path(__file__).resolve().parents[1] / "tools" \
        / "fit_machine.py"
    spec = importlib.util.spec_from_file_location("fit_machine", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_fit_from_registry_recovers_known_machine():
    """Synthesize drift records whose measured times follow the alpha-beta
    model under known (net_bw, hop_latency); the registry fit must recover
    them (2 records, 2 unknowns -> exact up to lstsq fp error)."""
    fm = _load_fit_machine()
    alg = next(a for a in api.REGISTRY
               if a.style == "bsp" and a.cost_fn is None)
    base = roofline.TPU_V5E
    net_bw_true, alpha_true = 123e9, 3e-6
    for steps, byts, flops in ((4, 1.0e8, 1e9), (8, 8.0e8, 2e9)):
        cm = {"steps": steps, "total_net_bytes": byts, "total_flops": flops,
              "ai_local": 10.0}
        t_comp = cm["total_flops"] / roofline.local_peak(cm["ai_local"],
                                                         base)
        n_msgs = alg.msgs_per_step if alg.msgs_per_step is not None \
            else len(alg.wire)
        msgs = n_msgs * (1.0 if alg.wire_amortized else steps)
        measured = t_comp + (byts / alg.duplex) / net_bw_true \
            + msgs * alpha_true
        obs.record_drift(alg.name, "padded", "off",
                         predicted_s=measured, measured_s=measured, cm=cm)
    fitted, diag = fm.fit_from_registry(base)
    assert diag["n_used"] == 2
    assert fitted.net_bw == pytest.approx(net_bw_true, rel=1e-3)
    assert fitted.hop_latency == pytest.approx(alpha_true, rel=1e-3)


def test_fit_from_registry_needs_records():
    fm = _load_fit_machine()
    with pytest.raises(ValueError, match="usable records"):
        fm.fit_from_registry()


# ---------------------------------------------------------------------------
# instrumented plan path
# ---------------------------------------------------------------------------
def test_traced_plan_emits_spans_and_drift_with_zero_retraces():
    a_d, b, a_h, b_h = _g1_handles(seed=17)
    obs.enable(clear=True, drift=True)
    obs.reset_drift()
    plan = api.plan_matmul(a_h, b_h, algorithm="ring_c", impl="ref",
                           cache=False)
    for _ in range(3):
        out = plan(a_h, b_h)
    obs.disable()
    np.testing.assert_allclose(np.asarray(out), a_d @ b, rtol=0, atol=1e-4)
    names = [e["name"] for e in obs.events()]
    assert "plan_build" in names
    assert "plan_build.executable" in names
    assert names.count("multiply.ring_c") == 3
    # the named_scope wrapper + span plumbing must not retrace
    assert plan.traces == 1
    d = obs.drift_report()[f"ring_c/{plan.wire}/{plan.overlap}"]
    assert d["n"] == 3 and d["ratio"] > 0
    rec = obs.drift_records()[0]
    assert rec["cm"]["total_flops"] > 0            # cm kept for re-fitting
    # multiply spans carry the blocking measured time
    mults = [e for e in obs.events() if e["name"] == "multiply.ring_c"]
    assert all(e["args"]["measured_s"] > 0 for e in mults)


def test_spans_without_drift_neither_block_nor_run_the_cost_model(
        monkeypatch):
    """Spans on, drift off: ``plan(a, b)`` opens its multiply span around
    the dispatch and returns; a block or a cost model would raise here."""
    a_d, b, a_h, b_h = _g1_handles(seed=23)
    plan = api.plan_matmul(a_h, b_h, algorithm="ring_c", impl="ref",
                           cache=False)

    def refuse(*a, **k):
        raise AssertionError("a traced plan call blocked or ran the cost "
                             "model with drift recording off")

    monkeypatch.setattr(jax, "block_until_ready", refuse)
    monkeypatch.setattr(obs, "sync_elapsed", refuse)
    monkeypatch.setattr(api.MatmulPlan, "cost_model", refuse)
    obs.enable(clear=True)
    outs = [plan(a_h, b_h) for _ in range(3)]
    obs.disable()
    monkeypatch.undo()
    np.testing.assert_allclose(np.asarray(outs[-1]), a_d @ b, rtol=0,
                               atol=1e-4)
    mults = [e for e in obs.events() if e["name"] == "multiply.ring_c"]
    assert len(mults) == 3
    assert all("measured_s" not in e["args"] for e in mults)
    assert obs.drift_records() == []
    assert plan.traces == 1


_PLAN_COUNTS = """
import json, sys
from repro.runtime.platform import set_host_device_count
set_host_device_count(4)
from repro import obs
from repro.core import api
from repro.core.bsr import random_sparse
from repro.kernels.bsr_spmm import list_chunk, pair_group

out = {}
for g, m, density in ((1, 256, 0.2), (2, 64, 0.3)):
    a = api.DistBSR.from_dense(random_sparse(m, m, density, seed=3 + g),
                               g=g, block_size=4)
    plan = api.plan_matmul(a, a, algorithm="ring_c", output="sparse",
                           impl="ref", cache=False)
    snap = obs.registry().snapshot()
    sym = plan.symbolic
    out[g] = {"real_pairs": snap["plan.real_pairs"]["algorithm=ring_c"],
              "pair_steps": snap["plan.pair_steps"]["algorithm=ring_c"],
              "pair_grid_steps":
                  snap["plan.pair_grid_steps"]["algorithm=ring_c"],
              "group": pair_group(4, "float32"),
              "sym_real_pairs": sym.total_real_pairs(),
              "n_real_pairs_sum": int(sym.n_real_pairs.sum()),
              "pair_capacity": sym.pair_capacity,
              "list_shape": list(sym.pair_a.shape),
              "chunk": list_chunk(3)}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def plan_counts():
    """Gauges and symbolic counts of a g=1 and a g=2 sparse-output plan,
    built on four virtual CPU devices in a process of their own."""
    env = dict(os.environ, PYTHONPATH=str(pathlib.Path(__file__).resolve()
                                          .parents[1] / "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _PLAN_COUNTS], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("g,chunked", [(1, True), (2, False)])
def test_plan_gauges_count_real_pairs_and_grid_steps(plan_counts, g,
                                                     chunked):
    c = plan_counts[str(g)]
    assert c["real_pairs"] == c["sym_real_pairs"] == c["n_real_pairs_sum"]
    assert c["list_shape"] == [g, g, g, c["pair_capacity"]]
    # every device runs g kernel calls over lists of one uniform length
    assert c["pair_steps"] == g * c["pair_capacity"]
    assert (c["pair_capacity"] > c["chunk"]) == chunked
    if chunked:
        assert c["pair_capacity"] % c["chunk"] == 0
    # real pairs fill at most every grid step of every device
    assert 0 < c["real_pairs"] <= c["pair_steps"] * g * g


@pytest.mark.parametrize("g,chunked", [(1, True), (2, False)])
def test_plan_gauge_counts_grouped_kernel_grid_steps(plan_counts, g,
                                                     chunked):
    """``plan.pair_grid_steps`` is the kernel's grid steps per product on
    each device: ``cdiv(L, G)`` for each chunk of ``L`` entries of each of
    the g lists, ``G`` the kernel's group for the plan's blocks."""
    c = plan_counts[str(g)]
    cap, chunk, group = c["pair_capacity"], c["chunk"], c["group"]
    chunks = [chunk] * (cap // chunk) if chunked else [cap]
    assert len(chunks) >= (2 if chunked else 1)
    assert c["pair_grid_steps"] == g * sum(-(-n // group) for n in chunks)
    assert c["pair_steps"] == g * cap
    assert c["real_pairs"] == c["sym_real_pairs"]


def test_untraced_plan_records_nothing():
    a_d, b, a_h, b_h = _g1_handles(seed=19)
    plan = api.plan_matmul(a_h, b_h, algorithm="ring_c", impl="ref",
                           cache=False)
    out = plan(a_h, b_h)
    np.testing.assert_allclose(np.asarray(out), a_d @ b, rtol=0, atol=1e-4)
    assert obs.events() == [] and obs.drift_records() == []


def test_named_scope_label_survives_into_hlo():
    from repro.launch.hlo_analysis import scope_op_counts

    def body(x):
        with jax.named_scope("plan.ring_c.padded"):
            return (x @ x) + 1.0

    text = jax.jit(body).lower(
        jnp.ones((8, 8), jnp.float32)).compile().as_text()
    counts = scope_op_counts(text, scope="plan.ring_c")
    assert counts.get("plan.ring_c.padded", 0) >= 1
    # unfiltered counts see the same component among others
    assert scope_op_counts(text)["plan.ring_c.padded"] >= 1


# ---------------------------------------------------------------------------
# serving spans
# ---------------------------------------------------------------------------
def test_serve_engine_emits_admission_and_decode_spans():
    from repro.configs import get_config
    from repro.models import transformer as tf
    from repro.serving import ServeEngine

    cfg = get_config("llama3-8b", smoke=True)
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    eng = ServeEngine(cfg, params=params, max_batch=2, max_len=48)
    for _ in range(2):
        eng.submit(rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32),
                   max_new_tokens=2)
    obs.enable(clear=True)
    eng.run()
    obs.disable()
    names = {e["name"] for e in obs.events()}
    assert {"serve.admit", "serve.prefill", "serve.decode_step"} <= names
    prefill = [e for e in obs.events() if e["name"] == "serve.prefill"]
    admits = [e for e in obs.events() if e["name"] == "serve.admit"]
    assert len(prefill) == len(admits) == 2        # one per admitted request
    steps = [e for e in obs.events() if e["name"] == "serve.decode_step"]
    assert steps and all(e["args"]["step_s"] > 0 for e in steps)
    assert all(e["args"]["prefill_s"] > 0 for e in admits)


def test_serving_metrics_rides_its_own_registry():
    from repro.serving import ServingMetrics

    m = ServingMetrics()
    m.submitted(0, arrival=0.0, prompt_len=8)
    m.prefill_done(0, 0.5)
    snap = m.registry.snapshot()
    assert snap["serve.prefill_s"] == 0.5
    # windows are isolated: the process-wide registry is untouched
    assert "serve.prefill_s" not in obs.registry().snapshot()


# ---------------------------------------------------------------------------
# trace_view summarizer
# ---------------------------------------------------------------------------
def _load_tool(name):
    path = pathlib.Path(__file__).resolve().parents[1] / "tools" \
        / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_view_summarize_aggregates_per_name():
    tv = _load_tool("trace_view")
    evs = [
        {"ph": "X", "name": "a", "dur": 1000.0, "ts": 0},
        {"ph": "X", "name": "a", "dur": 3000.0, "ts": 10},
        {"ph": "X", "name": "b", "dur": 500.0, "ts": 20},
        {"ph": "M", "name": "meta", "ts": 0},           # ignored
    ]
    rows = tv.summarize(evs)
    assert [r["name"] for r in rows] == ["a", "b"]      # total desc
    a = rows[0]
    assert a["count"] == 2 and a["total_ms"] == 4.0
    assert a["mean_ms"] == 2.0 and a["max_ms"] == 3.0
    assert tv.slowest(evs, 1)[0]["dur"] == 3000.0
    out = tv.render({"traceEvents": evs,
                     "otherData": {"dropped_events": 2}})
    assert "WARNING: 2 events dropped" in out


# ---------------------------------------------------------------------------
# check_api: raw perf_counter timing ban
# ---------------------------------------------------------------------------
def _load_check_api():
    path = pathlib.Path(__file__).resolve().parents[1] / "tools" \
        / "check_api.py"
    spec = importlib.util.spec_from_file_location("check_api", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_check_api_flags_unblocked_perf_counter_pairs(tmp_path):
    (tmp_path / "benchmarks").mkdir()
    (tmp_path / "src" / "repro" / "obs").mkdir(parents=True)
    bad = (
        "import time\n"
        "def t(fn):\n"
        "    t0 = time.perf_counter()\n"
        "    fn()\n"
        "    return time.perf_counter() - t0\n"
    )
    good = (
        "import time, jax\n"
        "def t(fn):\n"
        "    t0 = time.perf_counter()\n"
        "    jax.block_until_ready(fn())\n"
        "    return time.perf_counter() - t0\n"
    )
    single = (
        "import time\n"
        "def stamp():\n"
        "    return time.perf_counter()\n"
    )
    (tmp_path / "benchmarks" / "bad.py").write_text(bad)
    (tmp_path / "benchmarks" / "good.py").write_text(good)
    (tmp_path / "benchmarks" / "single.py").write_text(single)
    # same smeared pattern inside the obs package itself is allowlisted
    (tmp_path / "src" / "repro" / "obs" / "impl.py").write_text(bad)
    found = _load_check_api().violations(str(tmp_path))
    assert len(found) == 1 and "bad.py" in found[0]
    assert "perf_counter" in found[0]
