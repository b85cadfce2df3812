#!/usr/bin/env python3
"""The lower-precision controls of a cell, read on the chip.

    python3 bench/control.py --workload <name> --seeds <n> [<n> ...] \\
        [--program-precision <p>]

For each seed, makes the cell's inputs as a run does and prints the
comparison's readings beside the cell's limits, one JSON line per seed.
Without ``--program-precision``, the kind's control (the reference
computed one precision below the configuration's, on the first device)
stands in the program's place.  With it, the program itself runs the cell
as ``bench/run.py`` does, with a one-second window, at JAX matmul
precision ``<p>`` in place of the configuration's (``default`` is the
program's own one-bfloat16-pass path).  A control has to fail: a limit it
passes cannot tell the configuration's precision from a lower one.  The
benchmark's own runs never run this.
"""
import argparse
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program-precision", default=None)
    args = ap.parse_args(argv)

    from harness import cell as cell_mod, check, spec

    cell = spec.load_cell(args.workload)
    program = args.program_precision is not None
    try:
        devices = cell_mod.cell_devices(cell.chips if program else 1)
    except cell_mod.NoAccelerator as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if program:
        cell = dataclasses.replace(cell, config=dict(
            cell.config, matmul_precision=args.program_precision))
    for seed in args.seeds:
        t0 = time.perf_counter()
        if program:
            _, correct, checks = cell_mod.run(
                cell, seed=seed, seconds=1.0, trace=False,
                t_start=time.time(), devices=devices, peaks=None)
        else:
            csr = cell.generator.weighted_csr(cell.config, seed)
            inputs = check.to_host(cell.kind.inputs(csr, cell.traffic, seed))
            readings = cell.kind.compare(cell.kind.control(csr, inputs),
                                         cell.kind.reference(csr, inputs))
            correct, checks = check.verdict(readings, cell.limits)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control": args.program_precision or "kind",
                          "control_correct": correct, "checks": checks,
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
