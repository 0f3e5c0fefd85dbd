"""Set-up: process start through the end of warm-up, compiles included."""


def read(run):
    return run.setup_seconds
