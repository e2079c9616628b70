"""From one run's records to its metrics.

``Run`` holds what a run leaves: every request due in the window with its
outcome (from the engine's completion log and queues), the offline profile,
and in a traced run the decide log and the device's intervals. The
functions here are the arithmetic every metric reader uses; a reader that
finds nothing to read returns ``None`` and the metric is left out.
Times are seconds after the engine's start unless a name says otherwise.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Tuple

import numpy as np


@dataclasses.dataclass
class Run:
    seconds: float                 # T: arrivals are due in [0, T)
    tau: float                     # deadline of every request
    arrival: np.ndarray            # [n] due time, by req_id
    model: np.ndarray              # [n]
    dispatch: np.ndarray           # [n] NaN where never dispatched
    finish: np.ndarray             # [n] NaN where never completed
    exit_idx: np.ndarray           # [n] -1 where never completed
    batch: np.ndarray              # [n] 0 where never completed
    setup_s: float = float("nan")
    latency_table: Optional[np.ndarray] = None   # [M, E, B] profiled L
    batch_sizes: Tuple[int, ...] = ()
    flops: Optional[Callable[[int, int, int], float]] = None
    device_kind: str = ""
    peak_key: str = "bf16_flops"
    decide: Optional[np.ndarray] = None          # [k, 3] start, dur, served
    window: Optional[Tuple[float, float]] = None  # traced analysis window
    device: Optional[object] = None               # trace.DeviceTrace

    @property
    def completed(self) -> np.ndarray:
        return np.isfinite(self.finish)

    def quanta(self) -> np.ndarray:
        """One row per quantum: dispatch, finish, model, exit, batch;
        ordered by dispatch."""
        done = self.completed
        rows = np.column_stack([self.dispatch[done], self.finish[done],
                                self.model[done], self.exit_idx[done],
                                self.batch[done]])
        rows = np.unique(rows, axis=0)
        return rows[np.argsort(rows[:, 0], kind="stable")]

    def in_window(self, t: np.ndarray) -> np.ndarray:
        if self.window is None:
            return np.ones(len(t), dtype=bool)
        a, b = self.window
        return (t >= a) & (t < b)


# -- end to end ---------------------------------------------------------------

def p95_ms(run: Run) -> Optional[float]:
    done = run.completed
    if not done.any():
        return None
    return float(np.percentile(run.finish[done] - run.arrival[done], 95)
                 * 1e3)


def attainment_pct(run: Run) -> Optional[float]:
    if len(run.arrival) == 0:
        return None
    late = run.finish - run.arrival
    met = np.isfinite(late) & (late <= run.tau)
    return float(met.sum() / len(run.arrival) * 100.0)


def served_rps(run: Run) -> Optional[float]:
    return float((run.finish <= run.seconds).sum() / run.seconds)


def mean_exit_depth(run: Run) -> Optional[float]:
    done = run.completed
    if not done.any():
        return None
    return float(np.mean(run.exit_idx[done] + 1))


# -- online loop, scheduler, model step ---------------------------------------

def queue_wait_ms(run: Run) -> Optional[float]:
    sent = np.isfinite(run.dispatch) & run.in_window(run.dispatch)
    if not sent.any():
        return None
    return float(np.mean(run.dispatch[sent] - run.arrival[sent]) * 1e3)


def queued_at(run: Run, t: np.ndarray) -> np.ndarray:
    """Requests due by ``t`` and not yet dispatched at ``t``."""
    due = np.searchsorted(np.sort(run.arrival), t, side="right")
    sent = np.sort(run.dispatch[np.isfinite(run.dispatch)])
    return due - np.searchsorted(sent, t, side="right")


def loop_gap_us(run: Run) -> Optional[float]:
    q = run.quanta()
    keep = run.in_window(q[:, 0])
    gaps = []
    if len(q) > 1:
        busy = queued_at(run, q[:-1, 1]) > 0
        pair = busy & keep[:-1] & keep[1:]
        gaps = q[1:, 0][pair] - q[:-1, 1][pair]
    if len(gaps) == 0:
        return None
    return float(np.mean(gaps) * 1e6)


def decide_us(run: Run) -> Optional[float]:
    if run.decide is None:
        return None
    log = run.decide
    sel = (log[:, 2] > 0) & run.in_window(log[:, 0])
    if not sel.any():
        return None
    return float(np.mean(log[sel, 1]) * 1e6)


def quantum_over_profile(run: Run) -> Optional[float]:
    if run.latency_table is None:
        return None
    q = run.quanta()
    q = q[run.in_window(q[:, 0])]
    if len(q) == 0:
        return None
    col = np.searchsorted(run.batch_sizes, q[:, 4].astype(int))
    prof = run.latency_table[q[:, 2].astype(int), q[:, 3].astype(int), col]
    return float(np.percentile((q[:, 1] - q[:, 0]) / prof, 95))


# -- device (traced runs) -----------------------------------------------------

def union(start: np.ndarray, end: np.ndarray, a: float, b: float
          ) -> np.ndarray:
    """Disjoint ``[k, 2]`` intervals covering the union of the given ones,
    clipped to ``[a, b]``."""
    s, e = np.clip(start, a, b), np.clip(end, a, b)
    keep = e > s
    s, e = s[keep], e[keep]
    order = np.argsort(s, kind="stable")
    out: List[List[float]] = []
    for lo, hi in zip(s[order], e[order]):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return np.asarray(out, dtype=np.float64).reshape(-1, 2)


def busy_s(run: Run) -> Optional[float]:
    if run.device is None or run.window is None:
        return None
    u = union(run.device.op_start, run.device.op_end, *run.window)
    return float((u[:, 1] - u[:, 0]).sum())


def window_s(run: Run) -> Optional[float]:
    return None if run.window is None else run.window[1] - run.window[0]


def device_idle_pct(run: Run) -> Optional[float]:
    busy = busy_s(run)
    if not busy:
        return None
    return float((1.0 - busy / window_s(run)) * 100.0)


def step_mfu_pct(run: Run) -> Optional[float]:
    """FLOPs of the quanta whose executable ran in the window over the
    device time of those launches times the peak. A launch belongs to the
    quantum whose host interval holds its start."""
    if run.device is None or run.flops is None or run.window is None:
        return None
    from bench.peaks import peak

    q = run.quanta()
    start, end = run.device.mod_start, run.device.mod_end
    inside = run.in_window(start)
    start, end = start[inside], end[inside]
    if len(q) == 0 or len(start) == 0:
        return None
    k = np.searchsorted(q[:, 0], start, side="right") - 1
    ok = (k >= 0) & (start <= q[np.maximum(k, 0), 1])
    if not ok.any():
        return None
    device_s = float((end[ok] - start[ok]).sum())
    work = sum(run.flops(int(q[i, 2]), int(q[i, 3]), int(q[i, 4]))
               for i in np.unique(k[ok]))
    return float(work / (device_s * peak(run.device_kind, run.peak_key))
                 * 100.0)


def breakdown(run: Run, top: int = 10) -> Optional[dict]:
    """The device operations that took most time in the window, and the
    longest idle gaps, each named by what the host was doing: ``decide``
    where the probe's decide calls cover most of the gap, ``no_request``
    where no request was queued, otherwise ``host``."""
    if run.device is None or run.window is None:
        return None
    a, b = run.window
    dev = run.device
    s, e = np.clip(dev.op_start, a, b), np.clip(dev.op_end, a, b)
    totals = {}
    for name, d in zip(dev.op_name, e - s):
        if d > 0:
            totals[name] = totals.get(name, 0.0) + float(d)
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    u = union(dev.op_start, dev.op_end, a, b)
    edges = np.concatenate([[a], u.ravel(), [b]]).reshape(-1, 2)
    gaps = [(lo, hi) for lo, hi in edges if hi > lo]
    gaps.sort(key=lambda g: g[0] - g[1])
    log = run.decide
    named = []
    for lo, hi in gaps[:top]:
        label = "host"
        if log is not None:
            cs, ce = log[:, 0], log[:, 0] + log[:, 1]
            cover = np.clip(np.minimum(ce, hi) - np.maximum(cs, lo), 0, None)
            if cover.sum() > 0.5 * (hi - lo):
                label = "decide"
        if label == "host" and queued_at(run, np.array([(lo + hi) / 2]))[0] <= 0:
            label = "no_request"
        named.append([label, float(hi - lo)])
    return {"device_ops": [[n, d] for n, d in ops], "idle_gaps": named}
