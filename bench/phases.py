"""The live engine's phase spans reduced to per-layer numbers.

A ``ServingEngine`` with a ``Tracer`` attached records its host loop as
``repro.core.telemetry.PhaseSpan`` tuples (round, quantum, name, start,
end) that tile the run, and opens the profiler annotation ``quantum#<k>``
round each quantum's launch and wait. These functions take those spans,
the ``quantum#`` annotations' starts on the ``decide#`` clock of
``bench/trace.py``, and a ``reduce.Run`` for the window, the requests and
the device's intervals. The harness does not yet attach a tracer or
collect the ``quantum#`` annotations, so no metric reads them: wiring
them in is an edit to ``bench/harness.py`` and ``bench/trace.py``.
A function that finds nothing to read returns ``None``.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from bench.reduce import Run, union, window_s

# The host loop's own bookkeeping in a dispatching round.
LOOP_PHASES = ("ingest", "snapshot", "prune", "pop", "record")
# Idle inside these phases is the launched program's own schedule (the
# host only waits for it), not time the host holds the chip back.
DEVICE_PHASES = ("wait",)


def arrays(spans: Sequence[tuple]):
    """The spans as arrays: round, quantum, name, start, end."""
    if not spans:
        return None
    rnd, q, name, start, end = zip(*spans)
    return (np.asarray(rnd), np.asarray(q), np.asarray(name),
            np.asarray(start, dtype=np.float64),
            np.asarray(end, dtype=np.float64))


def inside(run: Run, start: np.ndarray, end: np.ndarray) -> np.ndarray:
    """Spans that lie wholly inside the window: the span in which the
    profiler stops, which holds the stop's own seconds, is left out."""
    if run.window is None:
        return np.ones(len(start), dtype=bool)
    return run.in_window(start) & (end <= run.window[1])


def round_mean_us(run: Run, spans, names: Sequence[str]) -> Optional[float]:
    """Mean over dispatching rounds inside the window of the sum of their
    spans named in ``names``."""
    ph = arrays(spans)
    if ph is None:
        return None
    rnd, _, name, start, end = ph
    ok = inside(run, start, end)
    rounds = np.intersect1d(rnd[(name == "ingest") & ok],
                            rnd[(name == "record") & ok])
    if len(rounds) == 0:
        return None
    sel = np.isin(name, names) & np.isin(rnd, rounds)
    return float((end[sel] - start[sel]).sum() / len(rounds) * 1e6)


def loop_overhead_us(run: Run, spans) -> Optional[float]:
    """Mean over dispatching rounds inside the window of their
    ``ingest``, ``snapshot``, ``prune``, ``pop`` and ``record`` spans."""
    return round_mean_us(run, spans, LOOP_PHASES)


def decide_span_us(run: Run, spans) -> Optional[float]:
    """Mean ``decide`` span of the dispatching rounds inside the window
    (an idle round's decide is part of its ``poll``)."""
    ph = arrays(spans)
    if ph is None:
        return None
    _, _, name, start, end = ph
    sel = (name == "decide") & inside(run, start, end)
    if not sel.any():
        return None
    return float(np.mean(end[sel] - start[sel]) * 1e6)


def launch_us(run: Run, spans) -> Optional[float]:
    """Mean over quanta inside the window of ``input`` plus ``launch``:
    from ``execute``'s entry to the executable's return, without
    ``compile``."""
    ph = arrays(spans)
    if ph is None:
        return None
    _, q, name, start, end = ph
    ok = inside(run, start, end)
    quanta = np.intersect1d(q[(name == "input") & ok],
                            q[(name == "launch") & ok])
    if len(quanta) == 0:
        return None
    sel = np.isin(name, ("input", "launch")) & np.isin(q, quanta)
    return float((end[sel] - start[sel]).sum() / len(quanta) * 1e6)


def longest_spans(spans, top: int = 5) -> Optional[list]:
    """The ``top`` longest spans of the whole run: name, start,
    duration. Stalls show here."""
    ph = arrays(spans)
    if ph is None:
        return None
    _, _, name, start, end = ph
    order = np.argsort(start - end, kind="stable")[:top]
    return [[str(name[i]), float(start[i]), float(end[i] - start[i])]
            for i in order]


def quantum_offset(spans, quantum_k: np.ndarray,
                   quantum_start: np.ndarray) -> Optional[float]:
    """Median over quanta of the ``quantum#k`` annotation's start (on the
    ``decide#`` clock) minus quantum k's ``launch`` start: the device's
    intervals minus this lie on the engine's clock."""
    ph = arrays(spans)
    if ph is None or len(quantum_k) == 0:
        return None
    _, q, name, start, _ = ph
    launch = name == "launch"
    at = dict(zip(q[launch].tolist(), start[launch].tolist()))
    diff = [s - at[k] for k, s in zip(np.asarray(quantum_k).tolist(),
                                      np.asarray(quantum_start).tolist())
            if k in at]
    return float(np.median(diff)) if diff else None


def intersect(x: np.ndarray, y: np.ndarray) -> np.ndarray:
    """``[k, 2]`` intersection of two sorted sets of disjoint intervals."""
    out: List[Tuple[float, float]] = []
    i = j = 0
    while i < len(x) and j < len(y):
        lo, hi = max(x[i, 0], y[j, 0]), min(x[i, 1], y[j, 1])
        if hi > lo:
            out.append((lo, hi))
        if x[i, 1] < y[j, 1]:
            i += 1
        else:
            j += 1
    return np.asarray(out, dtype=np.float64).reshape(-1, 2)


def idle_by_phase(run: Run, spans, offset: Optional[float]
                  ) -> Optional[dict]:
    """``{phase: seconds}``: the time of the window in which no operation
    ran on the device while some request due by then had not completed,
    split by the span it fell in (the spans tile the run, so each instant
    has one). ``offset`` is ``quantum_offset``."""
    ph = arrays(spans)
    if ph is None or offset is None or run.device is None \
            or run.window is None:
        return None
    a, b = run.window
    busy = union(run.device.op_start - offset, run.device.op_end - offset,
                 a, b)
    edges = np.concatenate([[a], busy.ravel(), [b]]).reshape(-1, 2)
    idle = edges[edges[:, 1] > edges[:, 0]]
    done = np.where(np.isfinite(run.finish), run.finish, b)
    waiting = intersect(idle, union(run.arrival, done, a, b))
    _, _, name, start, end = ph
    totals = {}
    for lo, hi in waiting:
        k = int(np.searchsorted(end, lo, side="right"))
        while k < len(start) and start[k] < hi:
            d = min(hi, end[k]) - max(lo, start[k])
            if d > 0:
                totals[str(name[k])] = totals.get(str(name[k]), 0.0) + d
            k += 1
    return totals


def host_idle_by_phase(run: Run, spans, offset: Optional[float]
                       ) -> Optional[list]:
    """The host-caused idle time, ``idle_by_phase`` outside
    ``DEVICE_PHASES``, as ``[[name, seconds], ...]``, longest first."""
    totals = idle_by_phase(run, spans, offset)
    if totals is None:
        return None
    return [[n, float(d)] for n, d in
            sorted(totals.items(), key=lambda kv: -kv[1])
            if n not in DEVICE_PHASES]


def wait_idle(run: Run, spans, offset: Optional[float]) -> Optional[list]:
    """``[["wait", seconds]]``: idle time while a request waited that fell
    inside a launched program's ``wait``, which ``host_idle_pct`` leaves
    out."""
    totals = idle_by_phase(run, spans, offset)
    if totals is None:
        return None
    return [[n, float(totals.get(n, 0.0))] for n in DEVICE_PHASES]


def host_idle_pct(run: Run, spans, offset: Optional[float]
                  ) -> Optional[float]:
    """Share of the window in which no device op ran while a due request
    had not completed, outside the ``wait`` of a launched program: the
    part of ``device_idle_pct`` the host causes."""
    by_phase = host_idle_by_phase(run, spans, offset)
    if by_phase is None:
        return None
    return float(sum(d for _, d in by_phase) / window_s(run) * 100.0)
