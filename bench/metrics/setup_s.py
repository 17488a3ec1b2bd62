"""Set-up: process start to window start, compilation included."""


def read(run):
    return run.setup_s
