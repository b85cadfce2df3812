"""List entries the pair-accumulate kernel takes per grid step: the plan's
``plan.pair_steps`` (list entries walked per device) over its
``plan.pair_grid_steps`` (the kernel's grid steps per product on each
device).  Both gauges are read from the program's registry; ``None``
where either is missing."""
from harness import program


def read(run):
    counters = program.counters()
    entries = program.gauge(counters, "plan.pair_steps")
    steps = program.gauge(counters, "plan.pair_grid_steps")
    if entries is None or not steps:
        return None
    return entries / steps
