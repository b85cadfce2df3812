"""Share of the SpMM kernel's grid steps on the busiest device that
multiply a real block of A, in percent: the plan's
``plan.spmm_real_blocks`` over its ``plan.spmm_block_steps``, both read
from the program's registry.  The rest multiply capacity padding and the
coverage blocks that visit every block row."""
from harness import program


def read(run):
    counters = program.counters()
    real = program.gauge(counters, "plan.spmm_real_blocks")
    steps = program.gauge(counters, "plan.spmm_block_steps")
    if real is None or not steps:
        return None
    return 100.0 * real / steps
