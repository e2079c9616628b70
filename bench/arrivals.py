"""The one arrival generator: turns a traffic file into a seeded trace.

A traffic file (``bench/traffic/<mix>.json``) is data only:

    {"process": "poisson" | "mmpp",
     "rate_rps": total mean rate over all models,
     "mix": per-model weights (3:2:1 = [3, 2, 1]),
     "count": "fixed" | "poisson",          # poisson only
     "burst": ..., "duty": ..., "cycle": ...}  # mmpp only

``count: "poisson"`` and ``process: "mmpp"`` copy the arithmetic of the
program's ``PoissonProcess`` and ``MMPPProcess`` (exponential gaps per
model; an on-off chain of exponential holding times with Poisson counts and
uniform times in each segment), so that a change to the program cannot move
the yardstick; ``bench/tests/test_bench_arrivals.py`` holds them bitwise
equal. ``count: "fixed"`` is the Poisson process conditioned on its count:
every seed gets exactly ``round(rate_m * horizon)`` requests of model ``m``,
at uniform times, so seeds differ in the order and spacing of the work and
not in its amount.

Events are ``(t, model, data_id)`` sorted as tuples; ``req_id`` is the rank.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

DATA_POOL = 10_000


def model_rates(traffic) -> List[float]:
    mix = np.asarray(traffic["mix"], dtype=np.float64)
    return [float(r) for r in traffic["rate_rps"] * mix / mix.sum()]


def poisson_events(rates: Sequence[float], horizon: float, seed: int,
                   data_pool: int = DATA_POOL) -> List[tuple]:
    """Copy of ``PoissonProcess._event_tuples``."""
    rng = np.random.default_rng(seed)
    events: List[tuple] = []
    for m, lam in enumerate(rates):
        if lam <= 0:
            continue
        n_expect = int(lam * horizon * 1.25 + 50)
        gaps = rng.exponential(1.0 / lam, size=n_expect)
        times = np.cumsum(gaps)
        while times[-1] < horizon:
            extra = rng.exponential(1.0 / lam, size=n_expect)
            times = np.concatenate([times, times[-1] + np.cumsum(extra)])
        times = times[times < horizon]
        data = rng.integers(0, data_pool, size=len(times))
        events.extend(zip(times.tolist(), [m] * len(times), data.tolist()))
    return events


def fixed_count_events(rates: Sequence[float], horizon: float, seed: int,
                       data_pool: int = DATA_POOL) -> List[tuple]:
    """Poisson conditioned on its count: ``round(lam * horizon)`` uniform
    times per model."""
    rng = np.random.default_rng(seed)
    events: List[tuple] = []
    for m, lam in enumerate(rates):
        n = int(round(lam * horizon))
        times = rng.uniform(0.0, horizon, size=n)
        data = rng.integers(0, data_pool, size=n)
        events.extend(zip(times.tolist(), [m] * n, data.tolist()))
    return events


def mmpp_segments(rng: np.random.Generator, horizon: float, burst: float,
                  duty: float, cycle: float) -> List[Tuple[float, float, float]]:
    """Copy of ``MMPPProcess._segments``."""
    off = (1.0 - duty * burst) / (1.0 - duty)
    segs: List[Tuple[float, float, float]] = []
    t = 0.0
    on = bool(rng.random() < duty)
    while t < horizon:
        mean = duty * cycle if on else (1.0 - duty) * cycle
        dur = float(rng.exponential(mean))
        segs.append((t, min(t + dur, horizon), burst if on else off))
        t += dur
        on = not on
    return segs


def mmpp_events(rates: Sequence[float], horizon: float, seed: int,
                burst: float, duty: float, cycle: float,
                data_pool: int = DATA_POOL) -> List[tuple]:
    """Copy of ``MMPPProcess._event_tuples`` (with ``_segment_poisson``)."""
    if not (burst >= 1.0 and 0.0 < duty < 1.0 and cycle > 0.0
            and burst * duty <= 1.0):
        raise ValueError("mmpp needs burst >= 1, 0 < duty < 1, cycle > 0 "
                         "and burst * duty <= 1")
    rng = np.random.default_rng(seed)
    segs = mmpp_segments(rng, horizon, burst, duty, cycle)
    events: List[tuple] = []
    for m, lam in enumerate(rates):
        if lam <= 0:
            continue
        for t0, t1, mult in segs:
            dur = t1 - t0
            if dur <= 0 or mult <= 0:
                continue
            n = int(rng.poisson(lam * mult * dur))
            times = rng.uniform(t0, t1, size=n)
            data = rng.integers(0, data_pool, size=n)
            events.extend(zip(times.tolist(), [m] * n, data.tolist()))
    return events


def generate(traffic, horizon: float, seed: int) -> List[tuple]:
    """Sorted ``(t, model, data_id)`` arrivals in ``[0, horizon)``."""
    rates = model_rates(traffic)
    kind = traffic["process"]
    if kind == "poisson":
        count = traffic.get("count", "poisson")
        make = {"poisson": poisson_events, "fixed": fixed_count_events}[count]
        events = make(rates, horizon, seed)
    elif kind == "mmpp":
        events = mmpp_events(rates, horizon, seed, traffic["burst"],
                             traffic["duty"], traffic["cycle"])
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    events.sort()
    return events
