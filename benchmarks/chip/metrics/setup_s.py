"""Set-up: process start to the first measured submit (host clock)."""


def read(run):
    return run.setup_s
