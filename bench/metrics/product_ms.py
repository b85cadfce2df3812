"""The window's elapsed time over the products it completed, in ms."""


def read(run):
    return 1e3 * run.window_s / run.n_products
