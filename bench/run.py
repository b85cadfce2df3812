#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as one JSON line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, traffic mix, product kind, limits and metrics
are found by name from ``BENCHMARK.json`` (see ``bench/harness/spec.py``).
With ``--trace 0`` the result holds the cell's end-to-end metrics; with
``--trace 1`` the profiler records the measured window and the result
holds its per-layer metrics.  Every run checks the last product against
the kind's reference and prints each number compared beside its limit,
as the last lines on standard error and under ``checks`` in the result.
Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 2 and prints no result.
"""
import time

T_START = time.time()   # process start, as near as Python lets us read it

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

# The compilation cache lives at a fixed path inside the checkout, so the
# first run of a cell compiles and later runs find every program there.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def _configure_jax():
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def result_line(cell, run, correct: bool, checks: dict, trace: bool,
                devices) -> dict:
    """The contract's result object; ``checks`` comes last."""
    import jax

    metrics = {}
    for name in cell.metric_names(trace):
        value = cell.readers[name].read(run)
        if value is not None:
            metrics[name] = {"value": value,
                             "unit": cell.metrics[name]["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": max(run.peak_bytes)}
    out = {"correct": correct, "attempted": run.n_products,
           "failed": 0 if correct else 1, "metrics": metrics,
           "device": device}
    if trace:
        t = run.trace
        device["busy_s"] = sum(d.busy_s for d in t.devices) / len(t.devices)
        device["window_s"] = t.window_s
        out["breakdown"] = {"device_ops": t.top_ops(),
                            "idle_gaps": t.top_gaps()}
    out["checks"] = checks
    return out


def _report(run, trace: bool, err) -> None:
    """Informational lines on standard error."""
    print("info: setup " + json.dumps(
        {"total": run.setup_s, **run.phases}),
        file=err)
    print(f"info: window products={run.n_products} window_s={run.window_s!r} "
          f"compiles_in_window={run.compiles_in_window} "
          f"peak_bytes={run.peak_bytes}", file=err)
    if trace:
        for d in run.trace.devices:
            print(f"info: device {d.plane} idle_pct="
                  f"{100.0 * (1.0 - d.busy_s / run.trace.window_s)!r} "
                  f"busy_s={d.busy_s!r} compute_s={d.compute_s!r} "
                  f"collective_s={d.collective_s!r} "
                  f"collective_exposed_s={d.collective_exposed_s!r}", file=err)
        print(f"info: traced products={run.trace.n_products} "
              f"window_s={run.trace.window_s!r}", file=err)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep a copy of the traced window's .xplane.pb here")
    args = ap.parse_args(argv)

    from harness import cell as cell_mod, spec

    cell = spec.load_cell(args.workload)
    _configure_jax()
    import repro.core.api  # noqa: F401  (the system under test must be here)

    try:
        devices = cell_mod.cell_devices(cell.chips)
    except cell_mod.NoAccelerator as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    peaks = spec.peaks_for(devices[0].device_kind)
    run, correct, checks = cell_mod.run(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t_start=T_START, devices=devices, peaks=peaks,
        trace_dir=args.trace_dir)
    result = result_line(cell, run, correct, checks, bool(args.trace),
                         devices)
    _report(run, bool(args.trace), sys.stderr)
    for name, c in checks.items():
        print(f"check: {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
