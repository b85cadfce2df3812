"""Device time of compute operations (kernels and XLA fusions; not
collectives or copies) per product on the busiest device, in ms."""


def read(run):
    t = run.trace
    if t is None or not t.n_products:
        return None
    return 1e3 * max(d.compute_s for d in t.devices) / t.n_products
