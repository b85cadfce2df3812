"""Set-up phase ``lower_compile`` on the host clock, in seconds."""


def read(run):
    return run.phases["lower_compile"]
