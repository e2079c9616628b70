"""Share of requests due in [0, T) completed by their deadline; dropped and stranded requests miss."""

from bench import reduce


def read(run):
    return reduce.attainment_pct(run)
