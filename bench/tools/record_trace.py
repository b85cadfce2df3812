#!/usr/bin/env python3
"""Record a small traced run of a cell on the chip, for the tests of the
trace reduction (``bench/tests/test_trace.py``).

    python3 bench/tools/record_trace.py --workload <name> --scale 10 \\
        --seconds 0.3 --out bench/tests/data

Runs the cell as ``bench/run.py --trace 1`` does, with its graph cut to
``--scale`` so that the trace stays small, and keeps the window's
``.xplane.pb`` in ``--out`` as ``<workload>.s<scale>.xplane.pb`` beside a
JSON file of what the reduction read from it.
"""
import argparse
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--scale", type=int, default=10)
    ap.add_argument("--seconds", type=float, default=0.3)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from harness import cell as cell_mod, spec

    cell = spec.load_cell(args.workload)
    cell = dataclasses.replace(cell, config=dict(cell.config,
                                                 scale=args.scale))
    devices = cell_mod.cell_devices(cell.chips)
    run, _, _ = cell_mod.run(
        cell, seed=args.seed, seconds=args.seconds, trace=True,
        t_start=time.time(), devices=devices,
        peaks=spec.peaks_for(devices[0].device_kind), trace_dir=args.out)
    stem = os.path.join(args.out, f"{cell.name}.s{args.scale}")
    os.replace(os.path.join(args.out, f"{cell.name}.{args.seed}.xplane.pb"),
               stem + ".xplane.pb")
    t = run.trace
    summary = {
        "workload": cell.name, "scale": args.scale,
        "products_timed": run.n_products, "n_products": t.n_products,
        "window_s": t.window_s,
        "devices": [dataclasses.asdict(d) | {"gaps": len(d.gaps)}
                    for d in t.devices],
        "idle_by_span": t.idle_by_span}
    with open(stem + ".json", "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary)[:4000])
    return 0


if __name__ == "__main__":
    sys.exit(main())
