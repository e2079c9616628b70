"""Set-up: process start to the first due arrival, in s."""


def read(run):
    return run.setup_s
