"""Model step: 95th percentile over quanta of measured quantum time over its profiled L(m, e, B)."""

from bench import reduce


def read(run):
    return reduce.quantum_over_profile(run)
