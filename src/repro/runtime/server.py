"""Live serving engine: the paper's "GPU runtime" on a real accelerator.

Time-division execution of M early-exit models behind FIFO queues, driven
by any ``repro.core`` scheduler. The engine shares queues/snapshot/metrics
code with the simulator, but service time comes from executing the jitted
``forward_exit`` on the device instead of the profile table, and the loop
decides and launches the next quantum while the current one runs, where
the simulator's loop waits for it.

Offline phase  = ``measure_profile`` (wall-clock profile of every
(m, e, B) — one compiled executable per cell, exactly the paper's 120-cell
table), then ``ServingEngine.run`` is the online phase. Both run a cell
through ``ServedModel.execute``, whose executable cache they share, so a
deployment compiles each (m, e, B) once per process. With an
``OnlineProfiler`` attached (``repro.core.adaptive``), the offline table is
only the *cold start*: measured wall-clock service times feed back into
refreshed scheduler tables while serving, tracking device drift (thermal
throttling, DVFS, contention) the offline profile cannot see. Semantics and
usage: docs/runtime.md.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import time
from typing import (Any, Callable, Deque, Dict, List, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.adaptive import OnlineProfiler
from repro.core.metrics import summarize
from repro.core.profile import ProfileTable
from repro.core.queues import QueueSnapshot, ServiceQueue
from repro.core.request import Completion, Decision, Request
from repro.core.scheduler import Scheduler
from repro.core.telemetry import Tracer


def pack_values(values) -> Tuple[Tuple[jax.Array, ...], Callable]:
    """``values`` as few argument buffers for an executable.

    Leaves of rank 0 or 1 (norm scales and biases) that share a (shape,
    dtype) are stacked into one ``[n, *shape]`` device array, all groups
    by one jitted call; each leaf of higher rank (convolution kernels,
    heads) stays its own buffer, as it is. Buffers come in the order of
    their first leaf in ``values``. Returns ``(packed, unpack)``:
    ``unpack(packed)``, traced inside an executable, rebuilds the pytree
    from static rows of the stacks.

    Each call of an executable hands over every argument buffer at a host
    cost per buffer, so resnet152's 469 weights go as 165 buffers. Matrices
    are not stacked: XLA reads rows of a stacked matrix in place instead of
    staging them in on-chip memory, which made resnet152's quanta 9% (B =
    10) to 39% (B = 1) longer on a TPU v5e.
    """
    leaves, treedef = jax.tree_util.tree_flatten(values)
    buffers: List[Any] = []            # a matrix, or the rows of a stack
    where: List[Tuple[int, Optional[int]]] = []   # leaf -> (buffer, row)
    groups: Dict[Tuple[Tuple[int, ...], np.dtype], int] = {}
    for leaf in leaves:
        shape = np.shape(leaf)
        if len(shape) > 1:
            where.append((len(buffers), None))
            buffers.append(leaf)
            continue
        key = (shape, np.result_type(leaf))
        if key not in groups:
            groups[key] = len(buffers)
            buffers.append([])
        g = groups[key]
        where.append((g, len(buffers[g])))
        buffers[g].append(leaf)
    stacked = list(groups.values())
    stacks = jax.jit(lambda rows: [jnp.stack(r) for r in rows])(
        [buffers[g] for g in stacked])
    for g, stack in zip(stacked, stacks):
        buffers[g] = stack
    packed = tuple(buffers)

    def unpack(packed):
        return jax.tree_util.tree_unflatten(treedef, [
            packed[s] if row is None
            else jax.lax.index_in_dim(packed[s], row, keepdims=False)
            for s, row in where])

    return packed, unpack


@dataclasses.dataclass
class ServedModel:
    """One deployed early-exit model behind its FIFO queue (paper Sec. III).

    Attributes:
      name:       display/profile-row name (e.g. ``"resnet50"``).
      values:     model parameters (pytree) passed to ``forward_fn``.
      forward_fn: ``(values, data, exit_idx) -> outputs`` — one full
                  inference truncated at exit ``exit_idx`` (jit-able; the
                  engine compiles one executable per (m, e, B) cell).
      data_fn:    ``(batch_size) -> input payload batch`` for profiling and
                  serving quanta.
      num_exits:  number of early-exit heads, shallowest -> deepest.
      executables: ``(exit, batch) -> compiled executable``, filled on
                  first use by :meth:`execute`; each takes ``(packed, x)``.
      packed:     ``values`` as :func:`pack_values` hands them over, built
                  by the first :meth:`execute` (``values`` is read once).
      launch_buffers: device buffers each call hands its executable:
                  ``len(packed)`` plus the input's leaves (0 until packed).
      phase_hook: set by a traced :meth:`ServingEngine.run` while it lasts,
                  else ``None``: called with ``"compile"`` and ``"launch"``
                  as :meth:`execute` enters each phase, and ``"wait"``
                  when the executable's call returns.
    """

    name: str
    values: Any
    forward_fn: Callable[[Any, Any, int], Any]
    data_fn: Callable[[int], Any]
    num_exits: int
    executables: Dict[Tuple[int, int], Any] = dataclasses.field(
        default_factory=dict, init=False, repr=False, compare=False)
    packed: Optional[Tuple[jax.Array, ...]] = dataclasses.field(
        default=None, init=False, repr=False, compare=False)
    launch_buffers: int = dataclasses.field(
        default=0, init=False, repr=False, compare=False)
    _unpack: Optional[Callable[[Tuple[jax.Array, ...]], Any]] = (
        dataclasses.field(default=None, init=False, repr=False,
                          compare=False))
    phase_hook: Optional[Callable[[str], None]] = dataclasses.field(
        default=None, init=False, repr=False, compare=False)

    def execute(self, e: int, b: int):
        """One quantum: exit ``e`` at batch ``b``, launched on the device.

        Returns the quantum's outputs without waiting for them: JAX
        dispatch is asynchronous, so the device runs the program while
        the caller goes on. A caller that times the quantum blocks on the
        result itself (``jax.block_until_ready``).

        The first call packs ``values`` into :attr:`packed`. The first
        call for an (e, b) cell compiles it ahead of time; later calls
        reuse that executable, which refuses inputs of another shape
        rather than recompiling behind the caller's back.
        """
        hook = self.phase_hook
        x = self.data_fn(b)
        fn = self.executables.get((e, b))
        if fn is None or self.packed is None:
            if hook is not None:
                hook("compile")
            if self.packed is None:
                self.packed, self._unpack = pack_values(self.values)
                self.launch_buffers = (len(self.packed)
                                       + len(jax.tree_util.tree_leaves(x)))
            if fn is None:
                fn = jax.jit(
                    lambda p, x, _e=e: self.forward_fn(self._unpack(p), x, _e)
                ).lower(self.packed, x).compile()
                self.executables[(e, b)] = fn
        if hook is not None:
            hook("launch")
        out = fn(self.packed, x)
        if hook is not None:
            hook("wait")
        return out


class _ExecuteMarks:
    """A traced run's :attr:`ServedModel.phase_hook`: stamps each phase
    boundary inside ``execute`` on the engine's clock, and opens the
    profiler annotation ``quantum#<quantum>`` at ``launch``, which the
    engine closes once it sees the quantum done."""

    def __init__(self, clock: Callable[[], float], t0: float):
        self.clock = clock
        self.t0 = t0
        self.quantum = 0
        self._marks: List[Tuple[str, float]] = []
        self._annotation = None

    def __call__(self, name: str) -> None:
        if name == "launch":
            self._annotation = jax.profiler.TraceAnnotation(
                f"quantum#{self.quantum}")
            self._annotation.__enter__()
        self._marks.append((name, self.clock() - self.t0))

    def take(self) -> Tuple[List[Tuple[str, float]], Any]:
        """The last ``execute``'s marks and its open annotation; forget
        both."""
        out = self._marks, self._annotation
        self._marks, self._annotation = [], None
        return out


def _ready(leaves: Sequence[Any]) -> bool:
    """Whether every output leaf is computed (a leaf that is no device
    array, such as a host array a wrapped ``execute`` returns, is)."""
    for leaf in leaves:
        is_ready = getattr(leaf, "is_ready", None)
        if is_ready is not None and not is_ready():
            return False
    return True


@dataclasses.dataclass
class _Quantum:
    """A launched quantum, until the loop sees its outputs ready."""

    decision: Decision
    batch: List[Request]
    leaves: List[Any]              # the outputs' leaves
    launch: float                  # engine clock when ``execute`` was called
    snapshot: QueueSnapshot        # what the decision was made against
    index: int = -1                # traced: its place in trace.decisions
    annotation: Any = None         # traced: its open ``quantum#<index>``


def measure_profile(
    models: Sequence[ServedModel],
    batch_sizes: Sequence[int],
    exit_names: Optional[Sequence[str]] = None,
    accuracy: Optional[np.ndarray] = None,
    repeats: int = 10,
    warmup: int = 2,
    percentile: float = 95.0,
) -> ProfileTable:
    """Offline profiling phase (paper Sec. IV-B) against the live device.

    Compiles one executable per (m, e, B) cell (kept on the
    :class:`ServedModel` for ``ServingEngine.warmup`` to reuse) and records
    the ``percentile`` wall-clock latency over ``repeats`` runs after
    ``warmup`` discarded runs (``ProfileTable.measure`` underneath) — the
    paper's 120-cell table, measured rather than calibrated. The result is the
    scheduler's *cold-start* belief; attach an
    ``repro.core.adaptive.OnlineProfiler`` to :class:`ServingEngine` to keep
    it tracking the device online (docs/runtime.md "Online adaptation").
    """
    def run_fn(m: int, e: int, b: int):
        jax.block_until_ready(models[m].execute(e, b))

    n_exits = models[0].num_exits
    return ProfileTable.measure(
        [m.name for m in models],
        exit_names or [f"exit{i}" for i in range(n_exits)],
        list(batch_sizes),
        run_fn,
        accuracy=accuracy,
        repeats=repeats,
        warmup=warmup,
        percentile=percentile,
        meta={"platform": jax.devices()[0].platform},
    )


class ServingEngine:
    """Online serving loop (paper Sec. III "Online Serving Phase").

    The simulator's snapshot -> prune -> decide -> occupy round, but each
    quantum executes a jitted forward on the device, service time is
    whatever the wall clock says, and the next quantum is decided and
    launched while the current one runs (at most two in flight).
    ``profiler`` (optional) is an
    ``repro.core.adaptive.OnlineProfiler``: every quantum's measured
    service time is folded into it and the scheduler's table is swapped for
    its refreshed view on the profiler's cadence — online profile
    adaptation over the ``measure_profile`` cold start (docs/runtime.md).
    """

    def __init__(
        self,
        models: Sequence[ServedModel],
        scheduler: Scheduler,
        clock: Callable[[], float] = time.monotonic,
        profiler: Optional[OnlineProfiler] = None,
        tracer: Optional[Tracer] = None,
    ):
        self.models = list(models)
        self.scheduler = scheduler
        self.clock = clock
        self.profiler = profiler
        # Record-only telemetry (repro.core.telemetry): live runs emit the
        # same decision/span/event vocabulary as the simulators, so one
        # tools/tracestats.py invocation reads either. None = zero cost.
        self.tracer = tracer
        self.queues = [ServiceQueue(m) for m in range(len(models))]
        self.completions: List[Completion] = []
        self.dropped = 0
        self._busy_s = 0.0
        self._unsubmitted = 0  # trace tail never ingested (drain-cap exit)
        # Structured engine counters, cumulative across run() calls (like
        # the completion log); "engine-counters" trace events snapshot them
        # at each run() exit. stalls = rounds that launched and completed
        # no quantum (with nothing in flight they sleep); launched_ahead =
        # quanta launched while another was in flight; launch_buffers =
        # buffers the quanta handed their executables.
        self.counters: Dict[str, int] = {
            "batches_served": 0,
            "requests_served": 0,
            "stalls": 0,
            "profiler_refreshes": 0,
            "dropped": 0,
            "drain_residual": 0,
            "launch_buffers": 0,
            "launched_ahead": 0,
        }

    # -- ingress ---------------------------------------------------------------

    def submit(self, req: Request) -> None:
        """Enqueue one request (paper: arrivals are never gated on
        accelerator state; they become visible at the next round)."""
        self.queues[req.model].push(req)

    # -- execution ---------------------------------------------------------------

    def warmup(self, batch_sizes: Optional[Sequence[int]] = None) -> None:
        """Pre-compile every (m, e, B) so online serving never JITs.

        Cells that ``measure_profile`` already ran are reused, not
        compiled again (the cache lives on each :class:`ServedModel`).

        ``batch_sizes=None`` derives the reachable batch set from the
        scheduler itself: the union of its candidate ladders over every
        possible queue length up to B_max (greedy and lattice policies both
        cap batches at ``config.max_batch``, and any smaller batch can occur
        when a queue is short, so this is exactly the dispatchable set).
        """
        if batch_sizes is None:
            reach = set()
            for qlen in range(1, self.scheduler.config.max_batch + 1):
                reach.update(self.scheduler.batch_candidates(qlen))
            batch_sizes = sorted(reach)
        for mod in self.models:
            for e in range(mod.num_exits):
                for b in batch_sizes:
                    jax.block_until_ready(mod.execute(e, b))

    def run(
        self,
        arrivals: Sequence[Request],
        duration: float,
        drain: bool = True,
        idle_sleep: float = 1e-4,
        drain_cap: float = 600.0,
    ) -> "tuple[list[Completion], float]":
        """Serve a pre-generated arrival trace in real time.

        Arrival times in the trace are relative to loop start; requests are
        enqueued when the wall clock passes them (paper: requests arrive
        continuously, regardless of accelerator state).

        Quanta overlap the loop: :meth:`ServedModel.execute` returns as
        soon as the program is enqueued, and the loop goes on with its
        rounds (ingest, snapshot, prune, decide) while it runs, checking
        each round whether the oldest quantum's outputs are ready. A
        decision made while a quantum runs is launched at once, and the
        runtime queues it behind the running one; at most two quanta are
        in flight, and with two the loop blocks on the older. Such a
        decision is made on the queues as they will stand when the chip
        frees: the snapshot's clock is ``max(now, start + L)``, ``start``
        the running quantum's service start and ``L`` the scheduler's
        profiled latency of it. A quantum is complete when the loop sees
        it ready (its ``finish``); its service start (``dispatch``) is
        ``max(its launch, the previous quantum's finish)``, so quanta
        keep disjoint intervals. Only an idle round (nothing in flight,
        nothing to dispatch) sleeps ``idle_sleep``. Every quantum launched
        completes before ``run`` returns.

        ``drain_cap`` mirrors the simulator's semantics: a hard wall-clock
        cap on post-``duration`` draining. Without it, ``drain=True``
        busy-waits forever whenever a policy leaves queues non-empty while
        ``decide`` keeps returning ``None`` (e.g. a pruning baseline that
        sheds nothing further but never dispatches). Requests stranded at
        the cap stay queued and are surfaced via ``metrics().residual_queue``.

        With a ``profiler`` attached, each quantum's measured wall-clock
        service (``finish - dispatch``) feeds ``OnlineProfiler.observe``
        and the scheduler's table is refreshed in place on the profiler's
        cadence.

        With a ``tracer`` attached, the loop also records its phases
        (``telemetry.PhaseSpan``), which tile ``[0, t_exit]``: each
        boundary is one clock read that ends one phase and starts the
        next. A round that launches a quantum runs ``ingest``,
        ``snapshot``, ``prune``, ``decide``, ``pop``, ``input``,
        ``launch``; the round that sees it done runs ``record`` and
        ``trace``, the tracer's own records, so the instrument's cost
        shows by name and stays out of the phases it measures. The rounds
        in between that launch and complete nothing are one span: the
        quantum's ``wait`` if its completion ends them, else a ``poll``.
        So each quantum's ``input``, ``launch``, ``wait`` and ``record``
        appear once, in that order, whether or not it was launched while
        another ran. A round that starts with every queue empty cannot
        launch and reads the clock once. Each quantum runs inside the
        profiler annotation ``quantum#<k>`` from its launch until the
        loop sees it done, ``k`` being its index in ``trace.decisions``,
        which puts a device trace on this clock; annotations of quanta in
        flight together overlap. The live engine records no decision
        margin: re-scoring every candidate would double the scheduler's
        cost in the run that measures it. Without a tracer the loop reads
        the clock three times per quantum and once per other round.
        """
        t0 = self.clock()
        next_arr = 0
        n = len(arrivals)
        self._unsubmitted = 0
        tracer = self.tracer
        inflight: Deque[_Quantum] = collections.deque()
        last_finish = -math.inf
        if tracer is not None:
            marks = _ExecuteMarks(self.clock, t0)
            rnd = -1
            # phase starts (t, round, quantum, name), in time order, handed
            # to the tracer in the next ``trace`` phase; ``idle`` is where
            # the rounds that launch and complete nothing began, and the
            # first round they cover, until it is known what ends them
            stamps: List[Tuple[float, int, int, str]] = []
            idle: Optional[Tuple[float, int]] = None
            for mod in self.models:
                mod.phase_hook = marks
        try:
            while True:
                done = bool(inflight) and _ready(inflight[0].leaves)
                now = self.clock() - t0
                if tracer is not None:
                    rnd += 1
                    t_round = now if rnd else 0.0
                while next_arr < n and arrivals[next_arr].arrival <= now:
                    self.submit(arrivals[next_arr])
                    next_arr += 1
                if done:
                    quantum = inflight.popleft()
                    dispatch = max(quantum.launch, last_finish)
                    last_finish = now
                    refreshed = self._complete(quantum, dispatch, now)
                    if tracer is not None:
                        stamps += [(*(idle or (now, rnd)), quantum.index,
                                    "wait"),
                                   (now, rnd, quantum.index, "record")]
                        idle = None
                        stamps.append((self.clock() - t0, rnd,
                                       quantum.index, "trace"))
                        self._trace_quantum(quantum, dispatch, now,
                                            refreshed, stamps)
                    continue
                capped = now > duration + drain_cap
                if capped or (now > duration and next_arr >= n and (
                        not drain
                        or all(len(q) == 0 for q in self.queues))):
                    if not inflight:
                        if capped:
                            # stranded work stays queued; the never-ingested
                            # trace tail is counted too so completions +
                            # dropped + residual still equals the arrival
                            # count (mirrors the simulator).
                            self._unsubmitted = n - next_arr
                        break
                    # launch nothing more; finish what is in flight
                    self.counters["stalls"] += 1
                    if tracer is not None and idle is None:
                        idle = (t_round, rnd)
                    jax.block_until_ready(inflight[0].leaves)
                    continue
                # a round that starts with every queue empty has nothing to
                # decide on: it cannot launch, and takes no phase stamps
                decision = None
                if any(self.queues):
                    if tracer is not None:
                        t_snapshot = self.clock() - t0
                    at = now
                    if inflight:
                        # the queues as they will stand when the chip frees
                        busy = inflight[0].decision
                        at = max(now, max(inflight[0].launch, last_finish)
                                 + self.scheduler.table(
                                     busy.model, busy.exit_idx,
                                     busy.batch_size))
                    snapshot = QueueSnapshot.take(self.queues, at)
                    if tracer is not None:
                        t_prune = self.clock() - t0
                    self._prune(snapshot, now)
                    if tracer is not None:
                        t_decide = self.clock() - t0
                    decision = self.scheduler.decide(snapshot)
                    if tracer is not None:
                        t_pop = self.clock() - t0
                if decision is None:
                    self.counters["stalls"] += 1
                    if tracer is not None and idle is None:
                        idle = (t_round, rnd)
                    if not inflight:
                        time.sleep(idle_sleep)
                    continue
                batch = self.queues[decision.model].pop_batch(
                    decision.batch_size)
                if tracer is not None:
                    q = marks.quantum = len(tracer.decisions) + len(inflight)
                t_launch = self.clock() - t0
                out = self.models[decision.model].execute(
                    decision.exit_idx, decision.batch_size)
                quantum = _Quantum(decision, batch,
                                   jax.tree_util.tree_leaves(out), t_launch,
                                   snapshot)
                if inflight:
                    self.counters["launched_ahead"] += 1
                inflight.append(quantum)
                if tracer is not None:
                    if idle is not None:
                        stamps.append((*idle, -1, "poll"))
                    stamps += [(t, rnd, q, name) for name, t in (
                        ("ingest", t_round), ("snapshot", t_snapshot),
                        ("prune", t_prune), ("decide", t_decide),
                        ("pop", t_pop), ("input", t_launch))]
                    launched, quantum.annotation = marks.take()
                    quantum.index = q
                    for name, t in launched:
                        if name == "wait":   # the host is free from here
                            idle = (t, rnd + 1)
                        else:
                            stamps.append((t, rnd, q, name))
                if len(inflight) == 2:
                    jax.block_until_ready(inflight[0].leaves)
        finally:
            if tracer is not None:
                for annotation in (marks.take()[1],
                                   *(held.annotation for held in inflight)):
                    if annotation is not None:
                        annotation.__exit__(None, None, None)
                for mod in self.models:
                    mod.phase_hook = None
        t_exit = self.clock() - t0
        self.counters["drain_residual"] = (
            sum(len(q) for q in self.queues) + self._unsubmitted)
        if tracer is not None:
            for s in stamps:
                tracer.record_phase(*s)
            tracer.record_phase(*(idle or (t_round, rnd)), -1, "poll")
            tracer.close_phase(t_exit)
            tracer.record_event(t_exit, "engine-counters", **self.counters)
        return self.completions, t_exit

    def _prune(self, snapshot: QueueSnapshot, now: float) -> None:
        """Drop what the policy's admission control sheds."""
        tracer = self.tracer
        for m, cnt in self.scheduler.prune(snapshot):
            popped = self.queues[m].pop_batch(cnt)
            n_shed = len(popped)
            self.dropped += n_shed
            self.counters["dropped"] += n_shed
            if tracer is not None:
                for req in popped:
                    tracer.record_drop(req, now, self.scheduler.config.slo)
                if n_shed:
                    tracer.record_event(now, "shed", n=n_shed)
            if self.profiler is not None:
                self.profiler.observe_dropped(n_shed)

    def _complete(self, quantum: _Quantum, dispatch: float,
                  finish: float) -> Optional[ProfileTable]:
        """Log a quantum the loop saw done, served from ``dispatch`` to
        ``finish``; returns the profiler's refreshed table, if any."""
        d = quantum.decision
        self._busy_s += finish - dispatch
        self.counters["batches_served"] += 1
        self.counters["launch_buffers"] += self.models[d.model].launch_buffers
        self.counters["requests_served"] += len(quantum.batch)
        for req in quantum.batch:
            self.completions.append(Completion(
                req_id=req.req_id, model=req.model,
                arrival=req.arrival, dispatch=dispatch,
                finish=finish, exit_idx=d.exit_idx,
                batch_size=d.batch_size, deadline=req.deadline,
            ))
        if self.profiler is None:
            return None
        refreshed = self.profiler.ingest_quantum(
            d.model, d.exit_idx, d.batch_size, finish - dispatch, finish,
            quantum.batch, self.scheduler.config.slo)
        if refreshed is not None:
            self.scheduler.table = refreshed
            self.counters["profiler_refreshes"] += 1
        return refreshed

    def _trace_quantum(self, quantum: _Quantum, dispatch: float,
                       finish: float, refreshed: Optional[ProfileTable],
                       stamps: List[Tuple[float, int, int, str]]) -> None:
        """The tracer's records of a completed quantum, and the phases
        stamped since the last such records."""
        tracer = self.tracer
        slo = self.scheduler.config.slo
        for s in stamps:
            tracer.record_phase(*s)
        stamps.clear()
        quantum.annotation.__exit__(None, None, None)
        quantum.annotation = None
        d, snapshot = quantum.decision, quantum.snapshot
        tracer.record_decision(
            dispatch, d, finish, tuple(snapshot.qlens()),
            tuple(snapshot.w_max(m) for m in range(len(self.queues))))
        for req in quantum.batch:
            tracer.record_completion(req, dispatch, finish, d.exit_idx,
                                     d.batch_size, slo)
        if refreshed is not None:
            tracer.record_refresh(finish, self.profiler)

    def metrics(self, table: ProfileTable, slo: float, span: float,
                warmup_tasks: int = 0):
        """Aggregate the completion log (paper Sec. VI metrics): the shared
        ``repro.core.metrics.summarize`` over live completions, with queued
        + never-ingested requests surfaced as ``residual_queue`` so
        completions + dropped + residual always equals the arrival count."""
        return summarize(
            self.completions, table, slo, warmup_tasks=warmup_tasks,
            busy_time=self._busy_s, span=span,
            residual_queue=(sum(len(q) for q in self.queues)
                            + self._unsubmitted),
            dropped=self.dropped,
        )

    def trace(self, **meta):
        """Freeze the attached tracer's timeline as a ``telemetry.Trace``
        (``None`` when no tracer is attached). Unlike the simulators the
        engine is long-lived, so the caller decides when to snapshot;
        residual-span accounting covers whatever is still queued now."""
        if self.tracer is None:
            return None
        slo = self.scheduler.config.slo
        for q in self.queues:
            for req in q.pending():
                self.tracer.record_residual(req, slo, device=-1)
        base = dict(engine="live", num_models=len(self.models),
                    num_devices=1, slo=slo)
        base.update(meta)
        return self.tracer.freeze(**base)
