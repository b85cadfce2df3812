"""One run of one cell: set-up, the measured window, the check, metrics.

The order is fixed by what each step must not disturb:

1. set-up, timed phase by phase on the host clock (after JAX is given
   the configuration's matmul precision): the graph from the
   configuration and the seed, the handle (``DistBSR.from_dense``), the
   kind's other inputs, the plan (``plan_matmul``), lowering and
   compiling it, and one warm-up product, which places the operands and
   compiles whatever runs around the executable;
2. the window: products back to back for ``seconds``, each timed from
   its dispatch to ``block_until_ready``; with ``trace`` the profiler
   records exactly this window;
3. the peak device memory, then the program's state dropped;
4. the check: the last product's output against the kind's reference;
5. the metrics, each from its own reader.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import shutil
import tempfile
import time
from typing import Optional

import numpy as np

from harness import check, trace as trace_mod


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers read this."""
    chips: int
    setup_s: float
    phases: dict                  # set-up phase -> seconds (host clock)
    product_s: list               # each product, dispatch to ready
    window_s: float               # first dispatch to last ready
    peak_bytes: list              # per device of the cell
    peaks: Optional[dict]         # one chip's peaks (bench/peaks.json)
    work: Optional[dict] = None   # the kind's least work, per product
    trace: Optional[trace_mod.Summary] = None
    compiles_in_window: int = 0

    @property
    def n_products(self) -> int:
        return len(self.product_s)


class _CompileCounter:
    """Counts JAX compilation events while ``active``."""

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.active and ("compile" in event or "jaxpr_trace" in event):
            self.count += 1


def _span(name: str, **kw):
    import jax

    return jax.profiler.TraceAnnotation(name, **kw)


@contextlib.contextmanager
def _phase(phases: dict, name: str):
    t0 = time.perf_counter()
    with _span("bench.setup." + name):
        yield
    phases[name] = time.perf_counter() - t0


def cell_devices(chips: int) -> list:
    """The cell's devices; raises :class:`NoAccelerator` without a TPU or
    with fewer chips than the cell asks for."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX found {devices[0].platform!r} "
                            "devices")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} TPU chips, JAX found "
                            f"{len(devices)}")
    return devices[:chips]


def _peak_bytes(devices) -> list:
    out = []
    for d in devices:
        stats = d.memory_stats() or {}
        out.append(int(stats.get("peak_bytes_in_use", 0)))
    return out


def run(cell, *, seed: int, seconds: float, trace: bool, t_start: float,
        devices: list, peaks: Optional[dict], impl: str = "pallas",
        trace_dir: Optional[str] = None) -> tuple:
    """Run the cell once.  Returns ``(run, correct, checks)``."""
    import jax

    from repro.core.api import DistBSR
    from repro.core.dist import make_grid_mesh

    cfg = cell.config
    g = cfg["g"]
    if g * g != cell.chips:
        raise ValueError(f"config {cfg['name']!r} has a {g}x{g} grid but "
                         f"the cell asks for {cell.chips} chips")
    # Every matmul without a precision of its own, the program's kernels
    # among them, runs at the precision the configuration states.
    jax.config.update("jax_default_matmul_precision",
                      cfg["matmul_precision"])
    counter = _CompileCounter()
    phases = {"start": time.time() - t_start}    # interpreter, imports, JAX
    with _phase(phases, "generate"):
        csr = cell.generator.weighted_csr(cfg, seed)
        dense = np.zeros(csr.shape, np.dtype(cfg["dtype"]))
        coo = csr.tocoo()
        dense[coo.row, coo.col] = coo.data
        del coo
    with _phase(phases, "tiling"):
        a_h = DistBSR.from_dense(dense, g=g, block_size=cfg["block_size"])
        a_h.tiled.blocks.block_until_ready()
    del dense
    mesh = make_grid_mesh(g)
    with _phase(phases, "inputs"):
        inputs = cell.kind.inputs(csr, cell.traffic, seed)
        product = cell.kind.Product(a_h, inputs, cell.traffic)
    with _phase(phases, "plan"):
        product.build_plan(mesh, impl)
    with _phase(phases, "lower_compile"):
        product.plan.lower(*product.args).compile()
    with _phase(phases, "warmup"):
        out = product()
        product.ready(out).block_until_ready()
    del out
    setup_s = time.time() - t_start

    tmp = None
    if trace:
        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
    product_s = []
    counter.active = True
    try:
        with _span(trace_mod.WINDOW_SPAN):
            t_win = time.perf_counter()
            out = None
            while not product_s or time.perf_counter() - t_win < seconds:
                with _span(trace_mod.PRODUCT_SPAN, i=len(product_s)):
                    out = None            # one output on the device at a time
                    t0 = time.perf_counter()
                    with _span("bench.dispatch"):
                        out = product()
                    with _span("bench.block"):
                        product.ready(out).block_until_ready()
                    product_s.append(time.perf_counter() - t0)
            window_s = time.perf_counter() - t_win
    finally:
        counter.active = False
        if trace:
            jax.profiler.stop_trace()
    peak_bytes = _peak_bytes(devices)
    host_inputs = check.to_host(inputs)
    got = cell.kind.view(out)
    del out, product, a_h, inputs

    ref = cell.kind.reference(csr, host_inputs)
    readings = cell.kind.compare(got, ref)
    del got, ref
    correct, checks = check.verdict(readings, cell.limits)

    r = Run(chips=cell.chips, setup_s=setup_s, phases=phases,
            product_s=product_s, window_s=window_s, peak_bytes=peak_bytes,
            peaks=peaks, compiles_in_window=counter.count)
    if trace:
        try:
            path = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                                    recursive=True))[-1]
            if trace_dir:
                os.makedirs(trace_dir, exist_ok=True)
                shutil.copy(path, os.path.join(
                    trace_dir, f"{cell.name}.{seed}.xplane.pb"))
            r.trace = trace_mod.reduce(path, [d.id for d in devices])
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
        r.work = cell.kind.work(csr, cell.traffic)
    return r, correct, checks
