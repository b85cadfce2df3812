"""Share of the pair-accumulate kernel's grid steps that multiply two real
blocks, in percent: the plan's ``plan.real_pairs`` (over all devices) over
its ``plan.pair_steps`` (per device, every device running as many) times
the cell's chips.  The rest are coverage pairs, chunk padding and list
padding.  Both gauges are read from the program's registry."""
from harness import program


def read(run):
    counters = program.counters()
    real = program.gauge(counters, "plan.real_pairs")
    steps = program.gauge(counters, "plan.pair_steps")
    if real is None or not steps:
        return None
    return 100.0 * real / (steps * run.chips)
