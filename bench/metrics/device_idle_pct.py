"""Device: share of the traced window in which no operation ran on the chip, in %."""

from bench import reduce


def read(run):
    return reduce.device_idle_pct(run)
