"""The trace reduction, on traces recorded on the chip and committed in
``bench/tests/data`` (``bench/tools/record_trace.py`` made them), and on
hand-made intervals."""
import glob
import json
import os

import numpy as np
import pytest

from harness import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACES = sorted(glob.glob(os.path.join(DATA, "*.xplane.pb")))


def test_union_and_subtract():
    assert trace.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert trace.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [
        (0, 1), (2, 4), (6, 9)]
    assert trace.subtract([(0, 3), (5, 8)], [(2, 6)]) == [(0, 2), (6, 8)]
    assert trace.length([(0, 2), (5, 6)]) == 3


@pytest.mark.parametrize("name,want", [
    ("collective-permute-start.3", "collective"),
    ("collective-permute-done", "collective"),
    ("all-gather.1", "collective"),
    ("all-reduce-start.2", "collective"),
    ("copy.7", "copy"),
    ("copy-start.1", "copy"),
    ("fusion.12", "compute"),
    ("bsr_spmm_kernel", "compute"),
    ("copy_fusion.2", "compute"),
    ("custom-call.4", "compute"),
    ("%collective-permute-start.1 = (f32[8,128]{1,0:T(8,128)}, u32[]{:S(2)})"
     " collective-permute-start(f32[8,128]{1,0:T(8,128)} %p), "
     "source_target_pairs={{0,1},{1,0}}", "collective"),
    ("%copy.3 = f32[8,128]{1,0:T(8,128)} copy(f32[8,128]{0,1} %p)", "copy"),
    ("%while.10 = (s32[]{:T(128)}, f32[7,128]{1,0:T(8,128)}) while((s32[]"
     "{:T(128)}, f32[7,128]{1,0:T(8,128)}) %tuple.26), condition=%c, "
     "body=%b", "compute"),
    ("%closed_call.10 = f32[7,128,128]{2,1,0:T(8,128)} custom-call(s32[4]"
     "{0:T(1024)S(1)} %d), custom_call_target=\"tpu_custom_call\"",
     "compute"),
])
def test_classify(name, want):
    assert trace.classify(name) == want


def test_parse_names_hlo_text():
    text = ("%bsr_pair_accumulate_pallas.1 = f32[306,128,128]{2,1,0:T(8,128)}"
            " custom-call(s32[5298]{0:T(1024)S(1)} %reduce)")
    assert trace.parse(text) == ("bsr_pair_accumulate_pallas.1",
                                 "custom-call")
    assert trace.parse("fusion.3") == ("fusion.3", "fusion.3")


def _brute_force(path, lo, hi):
    """Busy and compute time per device plane from a 1 us timeline; an op
    that holds another op of its line is busy but not compute."""
    from jax.profiler import ProfileData

    out = {}
    n = int((hi - lo) // 1000) + 1
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        busy = np.zeros(n, bool)
        comp = np.zeros(n, bool)
        events = 0
        for line in plane.lines:
            if line.name not in trace.OPS_LINES:
                continue
            evs = [(max(ev.start_ns, lo), min(ev.end_ns, hi), ev.name)
                   for ev in line.events]
            evs = sorted((ev for ev in evs if ev[1] > ev[0]),
                         key=lambda ev: (ev[0], -ev[1]))
            for i, (s0, e0, name) in enumerate(evs):
                holds = False
                for s1, e1, _ in evs[i + 1:]:
                    if s1 >= e0:
                        break
                    holds = holds or (e1 <= e0 and (s1, e1) != (s0, e0))
                s, e = int((s0 - lo) // 1000), int((e0 - lo) // 1000)
                events += 1
                busy[s:e] = True
                if not holds and trace.classify(name) == "compute":
                    comp[s:e] = True
        out[plane.name] = (busy.sum() * 1e-6, comp.sum() * 1e-6, events)
    return out


def test_recorded_traces_are_committed():
    assert TRACES, f"no recorded trace in {DATA}"


@pytest.mark.parametrize("path", TRACES, ids=os.path.basename)
def test_reduction_of_a_recorded_trace(path):
    with open(path[:-len(".xplane.pb")] + ".json") as f:
        recorded = json.load(f)
    s = trace.reduce(path)
    assert s.n_products == recorded["n_products"] >= 1
    assert s.window_s == pytest.approx(recorded["window_s"], rel=1e-12)
    assert len(s.devices) == len(recorded["devices"])
    for d, want in zip(s.devices, recorded["devices"]):
        assert d.plane == want["plane"]
        for key in ("busy_s", "compute_s", "collective_s",
                    "collective_exposed_s"):
            assert getattr(d, key) == pytest.approx(want[key], rel=1e-9)
        assert 0 < d.compute_s <= d.busy_s <= s.window_s
        assert 0 <= d.collective_exposed_s <= d.collective_s <= d.busy_s
        gaps = sum(e - b for b, e in d.gaps) * 1e-9
        assert gaps == pytest.approx(s.window_s - d.busy_s, abs=1e-9)
    mean_busy = sum(d.busy_s for d in s.devices) / len(s.devices)
    assert sum(s.idle_by_span.values()) == pytest.approx(
        s.window_s - mean_busy, abs=1e-9)
    assert all(name.startswith("bench.") or name == "no span"
               for name in s.idle_by_span)
    assert s.top_ops() and len(s.top_ops()) <= 10


@pytest.mark.parametrize("path", TRACES, ids=os.path.basename)
def test_reduction_agrees_with_a_timeline(path):
    """Busy and compute time agree with a 1 us boolean timeline to within
    the timeline's rounding."""
    from jax.profiler import ProfileData

    spans = trace._host_spans(ProfileData.from_file(path))
    (lo, hi), = [(s, e) for s, e, n in spans if n == trace.WINDOW_SPAN]
    brute = _brute_force(path, lo, hi)
    s = trace.reduce(path)
    for d in s.devices:
        busy, comp, events = brute[d.plane]
        slack = 2e-6 * (events + 1)         # each end rounds by up to 1 us
        assert d.busy_s == pytest.approx(busy, abs=slack)
        assert d.compute_s == pytest.approx(comp, abs=slack)
