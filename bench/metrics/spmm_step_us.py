"""The SpMM kernel's device time per grid step, in us: ``spmm_kernel_ms``
over the plan's ``plan.spmm_block_steps`` (read from the program's
registry), the grid steps one product runs on the busiest device."""
import os

from harness import program, spec

_KERNEL_MS = spec.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "spmm_kernel_ms.py"))


def read(run):
    ms = _KERNEL_MS.read(run)
    steps = program.gauge(program.counters(), "plan.spmm_block_steps")
    if ms is None or not steps:
        return None
    return 1e3 * ms / steps
