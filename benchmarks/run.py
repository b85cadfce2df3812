"""Benchmark harness — one module per paper table/figure.

Prints ``name,value,unit`` CSV.  Every experiment runs in a subprocess
with its own device count (jax locks the host device count at first
init); this parent process never touches jax, so on a host with a chip
no child finds the chip already held.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run fig1 fig2  # subset
  PYTHONPATH=src python -m benchmarks.run --smoke    # quick CI pass
  PYTHONPATH=src python -m benchmarks.run --json     # write BENCH_kernels.json

``--json`` runs the kernel micro-bench plus the balanced-tiling,
dense-vs-sparse-output SpGEMM, static-work-stealing, padded-vs-packed
wire and overlap-A/B experiments (R-MAT on a 4x4 grid, each in a
16-device subprocess) and writes ``BENCH_kernels.json`` at the repo
root: plan build time, per-multiply time, padded-flop waste, output
footprint, ``wire_bytes_padded`` vs ``wire_bytes_packed``,
per-schedule ``comm_exposed`` with overlap on vs off, and
predicted-vs-measured cost per algorithm — the perf-trajectory
baseline for future PRs.  The ``elastic`` section (``elastic_bench``,
9-device subprocess) records time-to-recover from a 5-of-9 device loss
against a cold rebuild, plus post-recovery per-multiply time.  It also
captures a ``serve_trace`` section (``serve_bench``: Poisson arrivals
through the sparse ``ServeEngine``) with p50/p99 TTFT/TPOT,
plans-per-second and the plan-cache hit rate.  Each
baseline refresh also re-fits the network constants of the cost model
(``tools/fit_machine.py``) from its own records and embeds the calibrated
preset plus per-record predicted-vs-measured drift under ``machine_fit``.
"""
from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys

SCALING_DEVICE_COUNTS = (1, 4, 9)
REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _subprocess_env(devices: int) -> dict:
    from repro.runtime.platform import subprocess_env
    env = subprocess_env(devices)
    src = os.path.join(REPO_ROOT, "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _run_subprocess(module: str, devices: int, *extra_args: str,
                    quiet: bool = False) -> str:
    out = subprocess.run(
        [sys.executable, "-m", module, *extra_args],
        env=_subprocess_env(devices), capture_output=True, text=True,
        timeout=1200)
    if out.returncode != 0:
        tail = out.stderr.strip().splitlines()[-1] if out.stderr else "?"
        print(f"{module},ERROR,{tail}")
        return ""
    if not quiet:
        sys.stdout.write(out.stdout)
    return out.stdout


def _load_fit_machine():
    """Import tools/fit_machine.py (tools/ is not a package)."""
    path = os.path.join(REPO_ROOT, "tools", "fit_machine.py")
    spec = importlib.util.spec_from_file_location("fit_machine", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _machine_fit_section(payload: dict) -> dict:
    """Re-fit Machine.net_bw/hop_latency from this payload's records and
    report per-record predicted-vs-measured drift (ROADMAP "Machine
    fitting in CI").  Never raises: a failed fit is recorded, not fatal.
    """
    try:
        from repro.core import roofline
        from repro.core.api import _predicted_time
        fm = _load_fit_machine()
        records = fm.collect_records(payload)
        fitted, diag = fm.fit(records, roofline.TPU_V5E)
        eff, ov_diag = fm.fit_overlap_eff(payload)
        if eff is not None:
            import dataclasses
            fitted = dataclasses.replace(fitted, overlap_eff=eff)
        diag.update(ov_diag)
        drift = []
        for rec in records:
            pred_nominal = _predicted_time(rec["cm"], rec["alg"],
                                           roofline.TPU_V5E)
            pred_fit = _predicted_time(rec["cm"], rec["alg"], fitted)
            drift.append({
                "source": rec["source"],
                "measured_s": rec["measured"],
                "predicted_s_nominal": pred_nominal,
                "predicted_s_fit": pred_fit,
                "drift_nominal": rec["measured"] / pred_nominal
                if pred_nominal else float("nan"),
                "drift_fit": rec["measured"] / pred_fit
                if pred_fit else float("nan"),
            })
        return {**diag, "records": drift}
    except Exception as e:                     # noqa: BLE001 (diagnostic)
        return {"error": f"{type(e).__name__}: {e}"}


def _write_json(smoke: bool) -> None:
    # "smoke" marks reduced-scale payloads so trajectory comparisons never
    # mistake a quick CI pass for the full baseline.
    payload = {"smoke": smoke}
    extra = ("--smoke",) if smoke else ()
    all_ok = True
    # kernels_bench prints the "kernels" and "obs_drift" sections in one
    # object; serve_bench drives the single-device serving engine; the
    # rest are 16-device grid experiments printing one section each.
    for module, section, devices in (
            ("benchmarks.kernels_bench", None, 1),
            ("benchmarks.balance_bench", "balance_rmat_4x4", 16),
            ("benchmarks.spgemm_bench", "spgemm_rmat_4x4", 16),
            ("benchmarks.steal_bench", "steal_rmat_4x4", 16),
            ("benchmarks.wire_bench", "wire_rmat_4x4", 16),
            ("benchmarks.overlap_bench", "overlap_rmat_4x4", 16),
            ("benchmarks.analysis_bench", "analysis", 16),
            ("benchmarks.elastic_bench", "elastic", 9),
            ("benchmarks.serve_bench", "serve_trace", 1)):
        args = ("--json",) + extra if section is None else extra
        raw = _run_subprocess(module, devices, *args, quiet=True)
        try:
            result = json.loads(raw) if raw else {}
        except json.JSONDecodeError:
            result, raw = {}, ""   # degrade like empty output (exit 1 below)
        sections = result if section is None else {section: result}
        for key in (("kernels", "obs_drift") if section is None
                    else (section,)):
            payload[key] = sections.get(key) or {"error": f"{module} failed"}
        all_ok = all_ok and bool(raw)
    # every baseline refresh re-fits the cost model's network constants
    # from its own records and records the drift
    payload["machine_fit"] = _machine_fit_section(payload)
    raw = all_ok       # all experiments must land in the baseline
    # Smoke and error payloads go to sibling files so neither a quick CI
    # pass nor a failed run can clobber the committed full-scale baseline.
    if smoke:
        name = "BENCH_kernels_smoke.json"
    elif not raw:
        name = "BENCH_kernels_error.json"
    else:
        name = "BENCH_kernels.json"
    path = os.path.join(REPO_ROOT, name)
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(f"wrote {path}")
    if not raw:
        # don't let CI record a baseline missing the headline experiment
        sys.exit(1)


def main() -> None:
    argv = sys.argv[1:]
    unknown = [a for a in argv if a.startswith("-")
               and a not in ("--smoke", "--json")]
    if unknown:
        sys.exit(f"unknown flags {unknown}; supported: --smoke --json")
    smoke = "--smoke" in argv
    as_json = "--json" in argv
    which = {a for a in argv if not a.startswith("-")}
    if which and (smoke or as_json):
        sys.exit(f"figure selectors {sorted(which)} cannot be combined "
                 "with --smoke/--json (fixed payloads)")
    if as_json:
        _write_json(smoke)
        return
    if smoke:
        # Quick self-contained pass for tools/run_tier1.sh: kernel oracle
        # rows + traced obs check (kernels_bench), one scale-8 balance
        # check, one scale-9 sparse-output spgemm check, one scale-8
        # steal3d check, no multi-minute figure sweeps.
        ok = bool(_run_subprocess("benchmarks.kernels_bench", 1, "--smoke"))
        # analysis_bench asserts the static verifier finds nothing on
        # healthy plans and validate="fast" stays under the 5% cached
        # plan-build overhead budget;
        # wire_bench additionally *asserts* packed wire bytes <= padded and
        # packed results allclose to padded; overlap_bench asserts the
        # overlap A-B contract (double-buffered results allclose to bulk,
        # exposed comm no worse beyond measurement tolerance);
        # elastic_bench asserts the device-loss recovery contract
        # (recovered product allclose, time-to-recover within slack of a
        # cold rebuild, replan counters recorded); serve_bench asserts
        # the serving contract (dense-reference match, plan hits >
        # misses, zero dropped tokens) — all exit non-zero on violation
        for module, devices in (("benchmarks.balance_bench", 16),
                                ("benchmarks.spgemm_bench", 16),
                                ("benchmarks.steal_bench", 16),
                                ("benchmarks.wire_bench", 16),
                                ("benchmarks.overlap_bench", 16),
                                ("benchmarks.analysis_bench", 16),
                                ("benchmarks.elastic_bench", 9),
                                ("benchmarks.serve_bench", 1)):
            raw = _run_subprocess(module, devices, "--smoke", quiet=True)
            name = module.rsplit(".", 1)[1]
            print(f"smoke,{name},{'ok' if raw else 'FAILED'}")
            ok = ok and bool(raw)
        # exercise the machine-fit wiring against the committed baseline
        # (a full refresh re-fits from its own fresh records)
        baseline = os.path.join(REPO_ROOT, "BENCH_kernels.json")
        if os.path.exists(baseline):
            with open(baseline) as f:
                fit = _machine_fit_section(json.load(f))
            fit_ok = "error" not in fit
            detail = (f"net_bw={fit['net_bw']:.2e}" if fit_ok
                      else fit["error"])
            print(f"smoke,fit_machine,{'ok' if fit_ok else 'FAILED'};"
                  f"{detail}")
            ok = ok and fit_ok
        if not ok:
            sys.exit(1)
        return
    which = which or {"fig1", "fig2", "fig34", "fig5", "table2", "kernels"}
    # fall through: full figure sweep (optionally filtered by name)
    for name, module in (("fig1", "benchmarks.fig1_load_imbalance"),
                         ("fig2", "benchmarks.fig2_roofline"),
                         ("fig34", "benchmarks.fig34_spmm_scaling"),
                         ("fig5", "benchmarks.fig5_spgemm_scaling"),
                         ("table2", "benchmarks.table2_breakdown"),
                         ("kernels", "benchmarks.kernels_bench")):
        if name in which:
            for p in (SCALING_DEVICE_COUNTS if name in ("fig34", "fig5")
                      else (1,)):
                _run_subprocess(module, p)

if __name__ == "__main__":
    main()
