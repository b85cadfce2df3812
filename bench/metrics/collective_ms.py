"""Device time of collective operations (their start and done ops on the
``XLA Ops`` line) per product on the busiest device, in ms.  ``None``
where no collective ran."""


def read(run):
    t = run.trace
    if t is None or not t.n_products:
        return None
    busiest = max(d.collective_s for d in t.devices)
    if busiest <= 0:
        return None
    return 1e3 * busiest / t.n_products
