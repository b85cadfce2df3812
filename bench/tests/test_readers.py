"""The readers of what the program records (spans, counters, named kernel
ops), on hand-made runs: each reads the right number, and ``None`` where
what it reads is missing, as a program without that span, counter or
kernel name leaves it."""
import os

import pytest

from harness import cell as cell_mod, program, spec, trace

S = 1e6          # a span's ts/dur are in us


def _load(name):
    return spec.load_module(os.path.join(spec.BENCH_DIR, "metrics",
                                         name + ".py"))


def _ev(name, ts, dur, depth=0, tid=1):
    return {"ph": "X", "name": name, "ts": ts * S, "dur": dur * S,
            "pid": 0, "tid": tid, "args": {"depth": depth}}


def _device(ops):
    return trace.Device(plane="/device:TPU:0", busy_s=1.0, compute_s=1.0,
                        collective_s=0.0, collective_exposed_s=0.0, ops=ops,
                        gaps=[])


def _run(ops=None, chips=1, n_products=2):
    t = None
    if ops is not None:
        t = trace.Summary(window_s=1.0, n_products=n_products,
                          devices=[_device(o) for o in ops],
                          idle_by_span={})
    return cell_mod.Run(chips=chips, setup_s=1.0, phases={},
                        product_s=[0.5] * n_products, window_s=1.0,
                        peak_bytes=[0], peaks=None, trace=t)


# Set-up as the program records it: tiling (scan, then upload), then the
# plan, whose symbolic phase holds two structure reads; one structure read
# before it; a span of another thread overlapping the scan.
SETUP = [
    _ev("handle.tile.scan", 0.0, 10.0),
    _ev("handle.tile.upload", 10.0, 2.0),
    _ev("other.thread", 1.0, 5.0, tid=2),
    _ev("plan_build", 20.0, 30.0),
    _ev("plan_build.structure", 20.5, 3.0, depth=1),
    _ev("plan_build.symbolic", 24.0, 20.0, depth=1),
    _ev("plan_build.structure", 24.5, 3.0, depth=2),
    _ev("plan_build.structure", 28.0, 3.0, depth=2),
    _ev("plan_build.executable", 45.0, 4.0, depth=1),
    _ev("plan_build.commit", 45.5, 2.5, depth=2),
]
COUNTERS = {"plan.real_pairs": {"algorithm=ring_c": 90.0},
            "plan.pair_steps": {"algorithm=ring_c": 100.0},
            "plan_caches": {"plans": {"hits": 0}}}
OPS = [{"bsr_pair_accumulate.1": 0.2, "bsr_pair_accumulate.9": 0.4,
        "bsr_pair_accumulate_pallas.1": 5.0, "while.10": 0.6,
        "fusion.3": 0.01}]


@pytest.mark.parametrize("span,want", [
    ("handle.tile.scan", 10.0),
    ("handle.tile.upload", 2.0),
    ("plan_build.structure", 9.0),
    ("plan_build.symbolic", 14.0),
    ("plan_build.commit", 2.5),
])
def test_span_self_time(span, want):
    assert program.self_s(SETUP, span) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("events", [None, [], [_ev("plan_build", 0., 1.)]],
                         ids=["untraced", "nothing", "absent"])
def test_span_self_time_reads_none_without_its_span(events):
    assert program.self_s(events, "plan_build.symbolic") is None


def test_setup_split_covers_each_phase():
    tool = spec.load_module(os.path.join(spec.BENCH_DIR, "tools",
                                         "setup_split.py"))
    out = tool.split({"tiling": 12.5, "plan": 30.0, "warmup": 1.0}, SETUP)
    assert out["phases"] == {"tiling": 12.5, "plan": 30.0}
    assert out["spans"]["plan_build.symbolic"] == pytest.approx(14.0)
    assert out["covered_pct"] == pytest.approx({"tiling": 96.0,
                                                "plan": 85.0})
    none = tool.split({"tiling": 1.0, "plan": 1.0}, [])
    assert set(none["spans"].values()) == {None}
    assert none["covered_pct"] == {"tiling": 0.0, "plan": 0.0}


@pytest.mark.parametrize("metric,run,counters,want", [
    ("pair_step_util", _run(), COUNTERS, 90.0),
    ("pair_step_util", _run(chips=4), dict(
        COUNTERS, **{"plan.real_pairs": {"algorithm=ring_c": 360.0}}), 90.0),
    ("pair_kernel_ms", _run(ops=OPS), {}, 300.0),
    ("pair_kernel_ms", _run(ops=OPS + [{"bsr_pair_accumulate.2": 0.8}]),
     {}, 400.0),
    ("pair_step_us", _run(ops=OPS), COUNTERS, 3000.0),
], ids=lambda v: v if isinstance(v, str) else None)
def test_reader_reads(monkeypatch, metric, run, counters, want):
    monkeypatch.setattr(program, "counters", lambda: counters)
    assert _load(metric).read(run) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("metric", [
    "pair_kernel_ms", "pair_step_util", "pair_step_us"])
@pytest.mark.parametrize("run,counters", [
    (_run(), {}),                                   # an untraced run
    (_run(ops=[{"bsr_pair_accumulate_pallas.1": 0.1,  # a program without
                "closed_call.10": 1.0}]),             # them
     {"plan_caches": {}}),
    (_run(ops=[{"bsr_pair_accumulate": 0.0}]),      # nothing, or two
     {"plan.real_pairs": {"algorithm=a": 1.0, "algorithm=b": 2.0},
      "plan.pair_steps": {"algorithm=a": None}}),
], ids=["untraced", "absent", "ambiguous"])
def test_reader_reads_none_without_its_source(monkeypatch, metric, run,
                                              counters):
    monkeypatch.setattr(program, "counters", lambda: counters)
    assert _load(metric).read(run) is None


def test_counters_are_the_programs_registry():
    from repro import obs

    g = obs.registry().gauge("bench.test.gauge", algorithm="x")
    g.set(3.0)
    assert program.gauge(program.counters(), "bench.test.gauge") == 3.0
