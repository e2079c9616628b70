"""Online loop: mean wait from due time to dispatch of requests dispatched in the traced window, in ms."""

from bench import reduce


def read(run):
    return reduce.queue_wait_ms(run)
