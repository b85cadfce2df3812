"""The least time a product's work can take on the cell's chips."""
from __future__ import annotations


def least_time_s(work: dict, peaks: dict, chips: int) -> float:
    """The larger of the work's flops over the chips' peak rate (of the
    precision the kind names) and its bytes over their HBM bandwidth."""
    flops = work["flops"] / (chips * peaks["flops_per_s"][work["flops_peak"]])
    hbm = work["bytes"] / (chips * peaks["hbm_bytes_per_s"])
    return max(flops, hbm)


def share_pct(run) -> float | None:
    """Least time over the busiest device's compute time per product, in
    percent; ``None`` without a trace, a work count or compute time."""
    if run.trace is None or run.work is None or not run.trace.n_products:
        return None
    busiest = max(d.compute_s for d in run.trace.devices)
    if busiest <= 0:
        return None
    per_product = busiest / run.trace.n_products
    return 100.0 * least_time_s(run.work, run.peaks, run.chips) / per_product
