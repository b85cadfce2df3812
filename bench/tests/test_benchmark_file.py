"""``BENCHMARK.json`` keeps to the benchmark's contract, every name in it
has its files, and the harness names none of them."""
import json
import os
import re
import subprocess
import sys

import pytest

from harness import spec

BENCH = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def test_top_level_keys_and_command():
    assert set(BENCH) == KEYS
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"][1].startswith("bench/")
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_units_and_entry_keys():
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and os.path.isfile(
            os.path.join(spec.ROOT, c["file"]))
        names.append(c["name"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names += [w["name"], w["config"], w["traffic"]]
    for group, keys in (("end_to_end", {"name", "unit", "better", "bound",
                                        "source"}),
                        ("per_layer", {"name", "unit", "better", "source",
                                       "layer", "moves"})):
        for m in BENCH[group]:
            assert set(m) - {"workloads"} == keys, m["name"]
            assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
            names.append(m["name"])
    assert all(NAME.match(n) for n in names), names
    assert len({c["name"] for c in BENCH["configs"]}) == len(BENCH["configs"])
    assert len(BENCH["workloads"]) <= 24
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2)


def test_bounds():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_and_reports_enough(workload):
    cell = spec.load_cell(workload)
    e2e = cell.metric_names(trace=False)
    assert "setup_s" in e2e and len(e2e) >= 2
    per_layer = cell.metric_names(trace=True)
    assert per_layer
    moved = {cell.metrics[n]["moves"] for n in per_layer}
    assert moved <= set(e2e)
    assert all(hasattr(cell.readers[n], "read") for n in e2e + per_layer)
    assert cell.limits


def test_harness_names_no_cell_config_traffic_kind_or_metric():
    names = {c["name"] for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        names |= {w["name"], w["traffic"]}
        names.add(spec.load_cell(w["name"]).traffic["kind"])
    names |= {m["name"] for g in ("end_to_end", "per_layer") for m in BENCH[g]}
    files = [os.path.join(spec.BENCH_DIR, "run.py")] + [
        os.path.join(spec.BENCH_DIR, "harness", f)
        for f in os.listdir(os.path.join(spec.BENCH_DIR, "harness"))
        if f.endswith(".py")]
    for path in files:
        text = open(path).read()
        for n in names:
            assert not re.search(rf"['\"]{re.escape(n)}['\"]", text), (path, n)


def _run(cwd: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", str(2**31 + 3),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    p = _run(spec.ROOT)
    assert p.returncode == 2 and p.stdout == "", (p.stdout, p.stderr)
    assert "no TPU" in p.stderr


def test_bench_files_alone_exit_nonzero_without_result(tmp_path):
    """Only ``BENCHMARK.json`` and ``bench/``: the program is missing."""
    import shutil

    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0 and p.stdout == "", (p.stdout, p.stderr)
