"""Scheduler: mean host time of a decide call that dispatched, in us."""

from bench import reduce


def read(run):
    return reduce.decide_us(run)
