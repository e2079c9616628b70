"""Mean over completed requests of exit index + 1 (1 = layer1 ... 4 = final)."""

from bench import reduce


def read(run):
    return reduce.mean_exit_depth(run)
