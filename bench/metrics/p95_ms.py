"""95th percentile over requests due in [0, T) that completed of completion time minus due time, in ms."""

from bench import reduce


def read(run):
    return reduce.p95_ms(run)
