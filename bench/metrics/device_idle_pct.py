"""Share of the traced window in which no operation runs on a device,
mean over the cell's devices, in percent."""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    busy = sum(d.busy_s for d in t.devices) / len(t.devices)
    return 100.0 * (1.0 - busy / t.window_s)
