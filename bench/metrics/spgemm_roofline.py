"""The spgemm product's least time on the cell's chips (format-independent
work from ``bench/kinds/spgemm.py`` at ``bench/peaks.json``) over the busiest
device's compute time per product, in percent."""
from harness import roofline


def read(run):
    return roofline.share_pct(run)
