"""Set-up phase ``plan`` on the host clock, in seconds."""


def read(run):
    return run.phases["plan"]
