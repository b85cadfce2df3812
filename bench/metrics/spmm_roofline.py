"""The spmm product's least time on the cell's chips (format-independent
work from ``bench/kinds/spmm.py`` at ``bench/peaks.json``) over the busiest
device's compute time per product, in percent.  ``None`` where no SpMM
kernel ran."""
import os

from harness import roofline, spec

_KERNEL_MS = spec.load_module(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "spmm_kernel_ms.py"))


def read(run):
    if _KERNEL_MS.read(run) is None:
        return None
    return roofline.share_pct(run)
