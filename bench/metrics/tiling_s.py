"""Set-up phase ``tiling`` on the host clock, in seconds."""


def read(run):
    return run.phases["tiling"]
