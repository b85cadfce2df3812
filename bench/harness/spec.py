"""Finds what one cell is made of, by the names in ``BENCHMARK.json``.

Every configuration, traffic mix, product kind, generator, limit set and
metric lives in a file of its own under ``bench/``; this module only
joins names to files, so a new cell, kind or metric is new files and new
entries, never an edit here.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from types import ModuleType

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_module(path: str) -> ModuleType:
    """Import a file by path (names may hold ``.`` and ``-``)."""
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    name = "bench_" + os.path.relpath(path, BENCH_DIR).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` and the files it names."""
    name: str
    chips: int
    config: dict
    traffic: dict
    kind: ModuleType
    generator: ModuleType
    limits: dict
    metrics: dict          # name -> benchmark entry, for this cell
    readers: dict          # name -> module with read(run)

    def metric_names(self, trace: bool) -> list:
        group = "per_layer" if trace else "end_to_end"
        return [n for n, m in self.metrics.items() if m["group"] == group]


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, bench_path: str = None) -> Cell:
    """The cell named ``workload``, with its files loaded."""
    bench = _json(bench_path or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"one of {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(os.path.join(ROOT, configs[w["config"]]["file"]))
    traffic = _json(os.path.join(BENCH_DIR, "traffic", w["traffic"] + ".json"))
    metrics = {}
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if _applies(m, workload):
                metrics[m["name"]] = dict(m, group=group)
    return Cell(
        name=workload, chips=w["chips"], config=config, traffic=traffic,
        kind=load_module(os.path.join(BENCH_DIR, "kinds",
                                      traffic["kind"] + ".py")),
        generator=load_module(os.path.join(BENCH_DIR, "generators",
                                           config["generator"] + ".py")),
        limits=_json(os.path.join(BENCH_DIR, "limits", workload + ".json")),
        metrics=metrics,
        readers={n: load_module(os.path.join(BENCH_DIR, "metrics", n + ".py"))
                 for n in metrics})


def peaks_for(device_kind: str, path: str = None) -> dict:
    """The peaks of one chip of ``device_kind``; an unknown kind raises."""
    table = _json(path or os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json; known: {sorted(table)}")
    return table[device_kind]
