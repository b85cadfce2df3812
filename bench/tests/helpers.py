"""Small copies of the benchmark's cells for the CPU."""
import dataclasses
import time

from harness import cell as cell_mod, spec


def small_cell(workload: str, *, scale: int = 9, block_size: int = 32):
    """The real cell with its graph cut to ``scale`` and ``block_size``."""
    cell = spec.load_cell(workload)
    config = dict(cell.config, scale=scale, block_size=block_size)
    return dataclasses.replace(cell, config=config)


def run_small(cell, *, seed: int = 7, trace: bool = False, impl: str = "ref",
              seconds: float = 0.0):
    """One run of ``cell`` on the CPU devices, as ``bench/run.py`` makes
    it, without the look for a TPU."""
    import jax

    return cell_mod.run(cell, seed=seed, seconds=seconds, trace=trace,
                        t_start=time.time(),
                        devices=jax.devices()[:cell.chips],
                        peaks=None, impl=impl)
