"""Process start to the first timed product: generation, tiling,
planning (symbolic phase included), lowering, compiling, warm-up."""


def read(run):
    return run.setup_s
