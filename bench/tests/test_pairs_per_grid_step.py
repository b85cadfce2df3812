"""The reader of ``pairs_per_grid_step`` on hand-made registries: list
entries over grid steps, and ``None`` where a program leaves either gauge
unset, as one whose kernel takes one entry per grid step does."""
import os

import pytest

from harness import cell as cell_mod, program, spec

READER = spec.load_module(os.path.join(spec.BENCH_DIR, "metrics",
                                       "pairs_per_grid_step.py"))
RUN = cell_mod.Run(chips=1, setup_s=1.0, phases={}, product_s=[0.5],
                   window_s=1.0, peak_bytes=[0], peaks=None, trace=None)


@pytest.mark.parametrize("counters,want", [
    ({"plan.pair_steps": {"algorithm=ring_c": 4325310.0},
      "plan.pair_grid_steps": {"algorithm=ring_c": 135234.0}},
     4325310.0 / 135234.0),
    ({"plan.pair_steps": {"algorithm=ring_c": 100.0},
      "plan.pair_grid_steps": {"algorithm=ring_c": 100.0}}, 1.0),
], ids=["grouped", "one_per_step"])
def test_reads_entries_per_grid_step(monkeypatch, counters, want):
    monkeypatch.setattr(program, "counters", lambda: counters)
    assert READER.read(RUN) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("counters", [
    {},
    {"plan.pair_steps": {"algorithm=ring_c": 100.0}},
    {"plan.pair_grid_steps": {"algorithm=ring_c": 4.0}},
    {"plan.pair_steps": {"algorithm=a": 1.0, "algorithm=b": 2.0},
     "plan.pair_grid_steps": {"algorithm=a": 1.0}},
], ids=["nothing", "parent", "no_entries", "ambiguous"])
def test_reads_none_without_both_gauges(monkeypatch, counters):
    monkeypatch.setattr(program, "counters", lambda: counters)
    assert READER.read(RUN) is None
