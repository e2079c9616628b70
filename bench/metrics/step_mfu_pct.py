"""Model step: FLOPs of the quanta run in the traced window over their device time times the peak, in %."""

from bench import reduce


def read(run):
    return reduce.step_mfu_pct(run)
