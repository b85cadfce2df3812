"""Device time of the SpMM kernel, the ops named ``bsr_spmm`` or
``bsr_spmm.<n>``, per product on the busiest device, in ms.  ``None``
where no op carries that name."""
import re

KERNEL = "bsr_spmm"
_NAME = re.compile(rf"{re.escape(KERNEL)}(\.\d+)?")


def read(run):
    t = run.trace
    if t is None or not t.n_products:
        return None
    per_device = [sum(s for op, s in d.ops.items() if _NAME.fullmatch(op))
                  for d in t.devices]
    if not any(per_device):
        return None
    return 1e3 * max(per_device) / t.n_products
