#!/usr/bin/env python3
"""Split a cell's set-up phases by the program's own spans, on the chip.

    python3 bench/tools/setup_split.py --workload <name> --seed <n> \\
        [--seconds 1] [--scale <s>]

Runs the cell as ``bench/run.py --trace 0`` does, with the program's spans
(``repro.obs``, drift recording off) on from before tiling to the end of
the window, and prints one JSON line: the harness's ``tiling`` and
``plan`` phases on the host clock, the self time of each set-up span
below, and the share of each phase that its spans cover.
"""
import argparse
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]

# Each set-up phase of the harness and the program spans that split it.
SPLIT = {
    "tiling": ("handle.tile.scan", "handle.tile.upload"),
    "plan": ("plan_build.structure", "plan_build.symbolic",
             "plan_build.commit"),
}


def split(phases: dict, events) -> dict:
    """Each span's self time and each phase's covered share, in percent;
    a span the program did not record reads ``None``."""
    from harness import program

    out = {"phases": {p: phases[p] for p in SPLIT}, "spans": {},
           "covered_pct": {}}
    for phase, names in SPLIT.items():
        spans = {n: program.self_s(events, n) for n in names}
        out["spans"].update(spans)
        out["covered_pct"][phase] = 100.0 * sum(
            v for v in spans.values() if v) / phases[phase]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--scale", type=int, default=None)
    args = ap.parse_args(argv)

    from harness import cell as cell_mod, spec
    from repro import obs

    cell = spec.load_cell(args.workload)
    if args.scale is not None:
        cell = dataclasses.replace(cell, config=dict(cell.config,
                                                     scale=args.scale))
    devices = cell_mod.cell_devices(cell.chips)
    obs.enable(clear=True)
    try:
        run, correct, _ = cell_mod.run(
            cell, seed=args.seed, seconds=args.seconds, trace=False,
            t_start=time.time(), devices=devices, peaks=None)
    finally:
        obs.disable()
    out = dict(split(run.phases, obs.events()), workload=cell.name,
               seed=args.seed, correct=correct,
               product_ms=1e3 * run.window_s / run.n_products)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
