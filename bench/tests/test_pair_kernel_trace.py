"""The pair-accumulate kernel's stable name on traces recorded on the chip
and committed in ``bench/tests/data``: ``pair_kernel_ms`` finds every call
of the kernel by it."""
import os
import re

import pytest

from harness import cell as cell_mod, spec, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _kernel_calls(path):
    """``(op name, inside another op)`` of every Pallas kernel call on the
    ops line of each device plane: the first chunk's call stands alone,
    the chunk loop's calls lie inside its ``while``."""
    from jax.profiler import ProfileData

    calls = []
    for plane in ProfileData.from_file(path).planes:
        if not trace._DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name not in trace.OPS_LINES:
                continue
            evs = [(ev.start_ns, ev.end_ns, ev.name) for ev in line.events]
            for s, e, text in evs:
                if 'custom_call_target="tpu_custom_call"' in text:
                    inside = any(s0 <= s and e <= e0 and (s0, e0) != (s, e)
                                 for s0, e0, _ in evs)
                    calls.append((trace.parse(text)[0], inside))
    return calls


@pytest.mark.parametrize("stem,named", [
    ("spgemm-rmat-s15.s13", True),       # recorded with the kernel named
    ("spgemm-rmat-s15.s10", False),      # recorded before it was
    ("spmm-w512-rmat-s15-2x2.s10", False),
])
def test_pair_kernel_name_matches_every_chunk(stem, named):
    """``pair_kernel_ms`` finds the pair-accumulate kernel by its name in
    the first chunk's call and in the chunk loop's; in traces recorded
    before the kernel had a name it finds nothing and reads ``None``."""
    path = os.path.join(DATA, stem + ".xplane.pb")
    reader = spec.load_module(os.path.join(spec.BENCH_DIR, "metrics",
                                           "pair_kernel_ms.py"))
    pat = re.compile(rf"{re.escape(reader.KERNEL)}(\.\d+)?")
    calls = _kernel_calls(path)
    assert calls
    s = trace.reduce(path)
    run = cell_mod.Run(chips=len(s.devices), setup_s=0.0, phases={},
                       product_s=[0.0] * s.n_products, window_s=s.window_s,
                       peak_bytes=[0], peaks=None, trace=s)
    if not named:
        assert not any(pat.fullmatch(n) for n, _ in calls)
        assert reader.read(run) is None
        return
    assert all(pat.fullmatch(n) for n, _ in calls), calls
    assert {inside for _, inside in calls} == {False, True}
    kernel = max(sum(v for k, v in d.ops.items() if pat.fullmatch(k))
                 for d in s.devices)
    assert reader.read(run) == pytest.approx(1e3 * kernel / s.n_products)
    kernel_ms = spec.load_module(os.path.join(spec.BENCH_DIR, "metrics",
                                              "kernel_ms.py")).read(run)
    assert reader.read(run) >= 0.99 * kernel_ms
