"""Set-up: process start to the first timed request (chip start-up,
compiling or loading programs, the warm-up requests)."""


def read(run):
    return run.setup_s
