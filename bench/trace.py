"""The profiler window of a traced run, and its reduction to intervals.

``DecideProbe`` stands in for the scheduler's ``decide`` during a traced
run: it times each call on the host clock and, while the profiler is open
(from before the engine starts until the first call at or after
``stop_at`` seconds, always between two quanta), wraps each call in a
``TraceAnnotation`` named ``decide#<k>``, ``k`` being the call's index in
its own log. Those annotations put the host's clock and the trace's on
one line: ``read`` returns the device's operations and executable launches
in seconds after the engine's start.
"""

from __future__ import annotations

import dataclasses
import pathlib
import re
import time
from typing import List, Optional

import numpy as np

ANNOTATION = "decide#"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # no Python call tracing: it would swamp
    opts.host_tracer_level = 2     # the loop it is meant to observe
    return opts


class DecideProbe:
    """Wraps ``scheduler.decide`` (an instance attribute, so the engine
    calls it unchanged) for the traced run: ``start`` opens the profiler
    before the engine starts; the first call at or after ``stop_at``
    seconds closes it."""

    def __init__(self, scheduler, logdir: pathlib.Path, stop_at: float):
        import jax

        self._jax = jax
        self._orig = scheduler.decide
        scheduler.decide = self
        self.logdir = logdir
        self.stop_at = stop_at
        self.t0 = None            # engine start, time.monotonic()
        self.tracing = False
        self.stopped = None       # seconds after t0
        self.t: List[float] = []  # call start, time.monotonic()
        self.dur: List[float] = []
        self.served: List[bool] = []

    def start(self) -> None:
        self._jax.profiler.start_trace(str(self.logdir),
                                       profiler_options=profile_options())
        self.tracing = True

    def __call__(self, snapshot):
        if self.tracing and time.monotonic() - self.t0 >= self.stop_at:
            self.stop()
        if self.tracing:
            with self._jax.profiler.TraceAnnotation(
                    f"{ANNOTATION}{len(self.t)}"):
                t = time.monotonic()
                d = self._orig(snapshot)
                t2 = time.monotonic()
        else:
            t = time.monotonic()
            d = self._orig(snapshot)
            t2 = time.monotonic()
        self.t.append(t)
        self.dur.append(t2 - t)
        self.served.append(d is not None)
        return d

    def stop(self) -> None:
        if self.tracing:
            self.stopped = time.monotonic() - self.t0
            self._jax.profiler.stop_trace()
            self.tracing = False

    def log(self) -> np.ndarray:
        """``[k, 3]``: start (s after engine start), duration, served."""
        return np.column_stack([np.asarray(self.t) - self.t0,
                                np.asarray(self.dur),
                                np.asarray(self.served, dtype=float)])


@dataclasses.dataclass
class DeviceTrace:
    """Device intervals in seconds after the engine's start."""

    op_name: List[str]
    op_start: np.ndarray
    op_end: np.ndarray
    mod_start: np.ndarray
    mod_end: np.ndarray
    planes: List[str]          # "plane: line (events)" for the log


_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_KIND = re.compile(r"kind=(k\w+)")


def op_label(hlo: str) -> str:
    """A device op's HLO text (``%fusion.11 = f32[..] fusion(..),
    kind=kLoop, ..``) as its opcode and fusion kind (``fusion:kLoop``), so
    that time adds up across quanta and programs."""
    rhs = hlo.split(" = ", 1)[-1]
    op = _OPCODE.search(" " + rhs)
    kind = _KIND.search(rhs)
    label = op.group(1) if op else rhs.split("(", 1)[0].strip()
    return f"{label}:{kind.group(1)}" if kind else label


def read(logdir: pathlib.Path, probe_log: np.ndarray) -> Optional[DeviceTrace]:
    """The device planes of the newest trace under ``logdir``, on the
    engine's clock; ``None`` when the trace holds no device or no
    annotation to align it by (a CPU run)."""
    from jax.profiler import ProfileData

    files = sorted(logdir.glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        return None
    data = ProfileData.from_file(str(files[-1]))
    ops, mods, annot, planes = [], [], {}, []
    for plane in data.planes:
        device = plane.name.startswith("/device:") and "CPU" not in plane.name
        for line in plane.lines:
            events = list(line.events)
            planes.append(f"{plane.name}: {line.name} ({len(events)})")
            if device and line.name == OPS_LINE:
                ops.extend((op_label(e.name), e.start_ns, e.duration_ns)
                           for e in events)
            elif device and line.name == MODULES_LINE:
                mods.extend((e.start_ns, e.duration_ns) for e in events)
            elif not device:
                for e in events:
                    if e.name.startswith(ANNOTATION):
                        annot[int(e.name[len(ANNOTATION):])] = e.start_ns
    if not ops or not annot:
        return None
    k = np.fromiter(annot, dtype=np.int64)
    ns = np.fromiter(annot.values(), dtype=np.float64)
    offset = float(np.median(ns * 1e-9 - probe_log[k, 0]))

    def rel(start, dur):
        s = np.asarray(start, dtype=np.float64) * 1e-9 - offset
        return s, s + np.asarray(dur, dtype=np.float64) * 1e-9

    op_start, op_end = rel([o[1] for o in ops], [o[2] for o in ops])
    mod_start, mod_end = rel([m[0] for m in mods], [m[1] for m in mods])
    return DeviceTrace([o[0] for o in ops], op_start, op_end, mod_start,
                       mod_end, planes)
