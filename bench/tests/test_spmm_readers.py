"""The six readers of the spmm cell: on hand-made runs, each reads the
right number and ``None`` where what it reads is missing; on a small 2x2
chip trace of the cell (``data/spmm-w512-rmat-s16-2x2.s10.*``, recorded
with ``bench/tools/record_trace.py``), each reads a number that agrees
with the trace's own totals and with hand counts of the graph."""
import os

import numpy as np
import pytest

from harness import cell as cell_mod, program, spec, trace

CELL = "spmm-w512-rmat-s16-2x2"
READERS = ("spmm_kernel_ms", "spmm_step_us", "spmm_block_util",
           "spmm_roofline", "collective_ms", "wire_gb")
RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        CELL + ".s10.xplane.pb")
PEAKS = {"flops_per_s": {"bf16": 1e12}, "hbm_bytes_per_s": 1e9}


def _load(name):
    return spec.load_module(os.path.join(spec.BENCH_DIR, "metrics",
                                         name + ".py"))


def _device(ops, compute_s=1.0, collective_s=0.0):
    return trace.Device(plane="/device:TPU:0", busy_s=1.5,
                        compute_s=compute_s, collective_s=collective_s,
                        collective_exposed_s=collective_s, ops=ops, gaps=[])


def _run(devices=None, n_products=2, work=None):
    t = None
    if devices is not None:
        t = trace.Summary(window_s=2.0, n_products=n_products,
                          devices=devices, idle_by_span={})
    return cell_mod.Run(chips=4, setup_s=1.0, phases={},
                        product_s=[0.5] * n_products, window_s=1.0,
                        peak_bytes=[0], peaks=PEAKS, trace=t, work=work)


LABEL = "algorithm=ring_c,wire=padded"
COUNTERS = {"plan.spmm_block_steps": {LABEL: 1000.0},
            "plan.spmm_real_blocks": {LABEL: 620.0},
            "plan.wire_bytes": {LABEL: 2.5e9},
            "plan_caches": {"plans": {"hits": 0}}}
OPS = {"bsr_spmm": 0.1, "bsr_spmm.7": 0.2, "bsr_spmm_pallas.1": 5.0,
       "collective-permute-done.3": 0.05, "fusion.2": 0.01}
TRACED = _run([_device(OPS, 0.8, 0.3),
               _device({"bsr_spmm.7": 0.1}, 0.9, 0.4)],
              work={"flops": 1e9, "bytes": 4e6, "flops_peak": "bf16"})


@pytest.mark.parametrize("metric,want", [
    ("spmm_kernel_ms", 150.0),               # 0.3 s over 2 products
    ("spmm_step_us", 150.0),                 # 150 ms over 1,000 steps
    ("spmm_block_util", 62.0),
    ("spmm_roofline", 100.0 * 1e-3 / 0.45),   # bytes bound: 4e6 B at 4 GB/s
    ("collective_ms", 200.0),                # the busier device's 0.4 s
    ("wire_gb", 2.5),
])
def test_reader_reads(monkeypatch, metric, want):
    monkeypatch.setattr(program, "counters", lambda: COUNTERS)
    assert _load(metric).read(TRACED) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("metric", READERS)
@pytest.mark.parametrize("run,counters", [
    (_run(), {}),                                        # untraced
    (_run([_device({"bsr_spmm_pallas.1": 0.1, "fusion.9": 1.0})]),
     {"plan_caches": {}}),                               # absent
    (_run([_device({"bsr_spmm": 0.0})]),                 # two series
     {k: {"algorithm=a": 1.0, "algorithm=b": 2.0} for k in COUNTERS}),
], ids=["untraced", "absent", "ambiguous"])
def test_reader_reads_none_without_its_source(monkeypatch, metric, run,
                                              counters):
    monkeypatch.setattr(program, "counters", lambda: counters)
    assert _load(metric).read(run) is None


def _recorded_run():
    """The recorded trace as a run of the cell at scale 10, and the gauges
    its plan set, counted by hand from the graph's blocks."""
    from repro.core.grid import bucket_capacity

    cell = spec.load_cell(CELL)
    config = dict(cell.config, scale=10)
    csr = cell.generator.weighted_csr(config, 1)
    bs, g, n = config["block_size"], config["g"], csr.shape[0]
    tile_b = n // g // bs
    rows, cols = csr.nonzero()
    counts = np.zeros((g, g), int)
    for br, bc in {(r // bs, c // bs) for r, c in zip(rows, cols)}:
        counts[br // tile_b, bc // tile_b] += 1
    store = bucket_capacity(counts.max()) + tile_b
    steps = g * store                           # 256 wide: one panel
    a_tile = store * (bs * bs * 4 + 8)
    b_tile = n // g * 256 * 4
    gauges = {"plan.spmm_block_steps": {LABEL: float(steps)},
              "plan.spmm_real_blocks": {LABEL: float(
                  counts.sum(axis=1).max())},
              "plan.wire_bytes": {LABEL: float((g - 1) * (a_tile + b_tile))}}
    s = trace.reduce(RECORDED)
    run = cell_mod.Run(chips=cell.chips, setup_s=0.0, phases={},
                       product_s=[0.0] * s.n_products, window_s=s.window_s,
                       peak_bytes=[0], peaks=spec.peaks_for("TPU v5 lite"),
                       trace=s, work=cell.kind.work(csr, cell.traffic))
    return run, gauges


def test_readers_on_a_recorded_2x2_trace(monkeypatch):
    run, gauges = _recorded_run()
    monkeypatch.setattr(program, "counters", lambda: gauges)
    got = {m: _load(m).read(run) for m in READERS}
    assert all(v is not None for v in got.values()), got
    t = run.trace
    assert len(t.devices) == 4 and t.n_products >= 1
    per_product_compute = max(d.compute_s for d in t.devices) / t.n_products
    assert 0 < got["spmm_kernel_ms"] <= 1e3 * per_product_compute
    steps = gauges["plan.spmm_block_steps"][LABEL]
    assert got["spmm_step_us"] == pytest.approx(
        1e3 * got["spmm_kernel_ms"] / steps, rel=1e-12)
    assert got["spmm_block_util"] == pytest.approx(
        100 * gauges["plan.spmm_real_blocks"][LABEL] / steps, rel=1e-12)
    assert 0 < got["spmm_roofline"] <= 100
    assert 0 < got["collective_ms"] <= 1e3 * max(
        d.busy_s for d in t.devices) / t.n_products
    assert got["wire_gb"] == gauges["plan.wire_bytes"][LABEL] / 1e9
    # the cell's share of the accepted readers that read any traced cell
    kernel_ms = _load("kernel_ms").read(run)
    assert kernel_ms == pytest.approx(1e3 * per_product_compute, rel=1e-12)
    assert got["spmm_kernel_ms"] <= kernel_ms
    assert 0 <= _load("device_idle_pct").read(run) < 100
