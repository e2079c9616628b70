"""The live engine's overlapped loop: a quantum is decided and launched
while the one before it runs.

A fake device stands in for the chip: each quantum's output becomes ready
at a time the test fixes (service times from the profile), on a clock
that moves only when the engine reads it, so which quanta overlap is
decided by the fake's readiness and never by the host's wall clock.
"""

from __future__ import annotations

import time

import pytest

from repro.core import (
    ProfileTable,
    Request,
    SchedulerConfig,
    Tracer,
    make_scheduler,
)
from repro.runtime.server import ServingEngine, measure_profile

SLO = 0.05


class Clock:
    """Advances ``step`` seconds on every read, and counts the reads."""

    def __init__(self, step: float = 1e-4):
        self.t = 0.0
        self.step = step
        self.reads = 0

    def __call__(self) -> float:
        self.reads += 1
        self.t += self.step
        return self.t


class Output:
    """A quantum's output: ready once the clock reaches ``ready_at``;
    blocking on it moves the clock there."""

    def __init__(self, clock: Clock, ready_at: float):
        self.clock = clock
        self.ready_at = ready_at

    def is_ready(self) -> bool:
        return self.clock.t >= self.ready_at

    def block_until_ready(self):
        self.clock.t = max(self.clock.t, self.ready_at)
        return self


class Device:
    """Runs the quanta it is given one after another. ``behind`` keeps,
    for each launch, how many earlier quanta were not done yet."""

    def __init__(self, clock: Clock):
        self.clock = clock
        self.free = 0.0
        self.outputs: list = []
        self.behind: list = []

    def launch(self, service: float) -> Output:
        self.behind.append(sum(not o.is_ready() for o in self.outputs))
        out = Output(self.clock, max(self.clock.t, self.free) + service)
        self.free = out.ready_at
        self.outputs.append(out)
        return out


class FakeModel:
    """What the engine uses of a ``ServedModel``: ``execute`` launches on
    the fake device and returns at once (``blocking`` waits for the
    output, as the loop's serial form did)."""

    name = "m0"
    num_exits = 2
    launch_buffers = 3

    def __init__(self, device: Device, table: ProfileTable,
                 blocking: bool = False):
        self.device = device
        self.table = table
        self.blocking = blocking
        self.phase_hook = None
        self.launched: list = []

    def execute(self, e: int, b: int):
        hook = self.phase_hook
        if hook is not None:
            hook("launch")
        out = self.device.launch(self.table(0, e, b))
        self.launched.append((e, b))
        if hook is not None:
            hook("wait")
        if self.blocking:
            out.block_until_ready()
        return out


@pytest.fixture(scope="module")
def table():
    return ProfileTable.paper_rtx3080().select_models([0]).restrict_exits(
        [0, 3])


def engine(table, tracer=None, blocking=False, profiler=None, step=1e-4):
    clock = Clock(step)
    model = FakeModel(Device(clock), table, blocking)
    sched = make_scheduler("edgeserving", table,
                           SchedulerConfig(slo=SLO, max_batch=4))
    eng = ServingEngine([model], sched, clock=clock, profiler=profiler,
                        tracer=tracer)
    return eng, model, clock


def steady(n: int = 40, gap: float = 1e-3):
    """One request every ``gap`` seconds: several land during each
    final-exit quantum (2.8-4.2 ms)."""
    return [Request(req_id=i, model=0, arrival=i * gap) for i in range(n)]


def quanta(completions):
    """(dispatch, finish, exit, batch) per quantum, by dispatch."""
    return sorted({(c.dispatch, c.finish, c.exit_idx, c.batch_size)
                   for c in completions})


def test_arrivals_mid_quantum_launch_ahead(table):
    eng, model, _ = engine(table)
    comps, _ = eng.run(steady(), duration=0.04)
    c = eng.counters
    assert c["launched_ahead"] > 0
    assert c["requests_served"] == len(comps) == 40
    assert c["batches_served"] == len(model.launched)


def test_never_more_than_one_quantum_behind_the_running_one(table):
    eng, model, _ = engine(table)
    eng.run(steady(60, 5e-4), duration=0.03)
    assert max(model.device.behind) == 1
    # every quantum launched while another ran is counted
    assert eng.counters["launched_ahead"] == sum(
        b > 0 for b in model.device.behind)


def test_service_starts_after_the_previous_finish(table):
    eng, _, _ = engine(table)
    comps, _ = eng.run(steady(), duration=0.04)
    spans = quanta(comps)
    assert eng.counters["launched_ahead"] > 0
    for (d0, f0, *_), (d1, f1, *_) in zip(spans, spans[1:]):
        assert d1 >= f0
        assert f1 > d1


def test_quiet_trace_runs_as_the_serial_loop(table):
    # each request arrives after the quantum before it is done: nothing
    # can be launched ahead, and the decisions are the serial loop's
    arrivals = [Request(req_id=i, model=0, arrival=i * 0.01)
                for i in range(12)]
    served = {}
    for blocking in (False, True):
        eng, model, _ = engine(table, blocking=blocking)
        eng.run(arrivals, duration=0.12)
        assert eng.counters["launched_ahead"] == 0
        assert eng.counters["requests_served"] == 12
        served[blocking] = model.launched
    assert served[False] == served[True]


def test_drain_cap_completes_the_quantum_in_flight(table):
    eng, model, clock = engine(table)
    # a burst at 0, then a tail the cap leaves unread
    arrivals = [Request(req_id=i, model=0, arrival=0.0) for i in range(9)]
    arrivals.append(Request(req_id=9, model=0, arrival=1.0))
    comps, _ = eng.run(arrivals, duration=0.0, drain_cap=2e-3)
    c = eng.counters
    assert c["launched_ahead"] > 0
    # the run ended at the cap with requests still queued
    assert c["drain_residual"] > 0
    # every quantum launched was completed before the run returned
    assert all(o.is_ready() for o in model.device.outputs)
    assert c["batches_served"] == len(model.launched)
    m = eng.metrics(table, slo=SLO, span=clock.t)
    assert len(comps) + eng.dropped + m.residual_queue == len(arrivals)
    assert c["requests_served"] + eng.dropped + c["drain_residual"] == len(
        arrivals)


class SlowOutput:
    """An output that takes ``delay`` seconds of wall clock to wait for."""

    def __init__(self, delay: float):
        self.delay = delay

    def block_until_ready(self):
        time.sleep(self.delay)
        return self


def test_measure_profile_records_blocked_time():
    class Model:
        name = "m0"
        num_exits = 1

        def execute(self, e, b):
            return SlowOutput(0.02)

    prof = measure_profile([Model()], batch_sizes=[1], repeats=2, warmup=0)
    assert prof.latency[0, 0, 0] >= 0.02


class RecordingProfiler:
    """Keeps what the engine feeds an ``OnlineProfiler``."""

    def __init__(self):
        self.quanta = []

    def ingest_quantum(self, m, e, b, service, now, batch, slo):
        self.quanta.append((service, now, [r.req_id for r in batch]))
        return None

    def observe_dropped(self, n):
        pass


def test_profiler_is_fed_finish_minus_dispatch(table):
    prof = RecordingProfiler()
    eng, _, _ = engine(table, profiler=prof)
    comps, _ = eng.run(steady(), duration=0.04)
    assert eng.counters["launched_ahead"] > 0
    by_id = {c.req_id: c for c in comps}
    assert len(prof.quanta) == eng.counters["batches_served"]
    for service, now, ids in prof.quanta:
        c = by_id[ids[0]]
        assert now == c.finish
        assert service == c.finish - c.dispatch


def test_decisions_in_flight_see_the_queues_when_the_chip_frees(table):
    eng, model, clock = engine(table)
    seen = []
    decide = eng.scheduler.decide

    def record(snapshot):
        busy = [o for o in model.device.outputs if not o.is_ready()]
        seen.append((snapshot.now, clock.t, len(busy)))
        return decide(snapshot)

    eng.scheduler.decide = record
    comps, _ = eng.run(steady(), duration=0.04)
    spans = quanta(comps)
    assert eng.counters["launched_ahead"] > 0
    ahead = [(at, t) for at, t, busy in seen if busy]
    assert ahead
    for at, t in ahead:
        # the running quantum: the last one to start before this decision
        d, _, e, b = max(s for s in spans if s[0] <= t)
        free = d + table(0, e, b)
        assert at >= free
        assert at == free or at <= t
    # with nothing in flight a decision sees the queues as they are now
    assert all(at <= t for at, t, busy in seen if not busy)


def test_traced_overlap_phases_tile_and_keep_their_order(table):
    tracer = Tracer()
    eng, _, clock = engine(table, tracer)
    arrivals = steady(24) + [Request(req_id=24 + i, model=0, arrival=0.08)
                             for i in range(6)]
    _, t_exit = eng.run(arrivals, duration=0.1)
    c = eng.counters
    assert c["launched_ahead"] > 0
    trace = eng.trace()
    ph = trace.phases
    assert ph[0].start == 0.0 and ph[-1].end == t_exit
    for a, b in zip(ph, ph[1:]):
        assert a.end == b.start
    names = {}
    for p in ph:
        if p.quantum >= 0:
            names.setdefault(p.quantum, []).append(p.name)
    assert list(names) == list(range(c["batches_served"]))
    for got in names.values():
        assert got == ["ingest", "snapshot", "prune", "decide", "pop",
                       "input", "launch", "wait", "record", "trace"]
    # an ahead quantum's launching round falls between the running one's
    # launch and wait
    order = [(p.quantum, p.name) for p in ph if p.quantum >= 0]
    assert any(order.index((q + 1, "launch")) < order.index((q, "wait"))
               for q in range(c["batches_served"] - 1))
    polls = [i for i, p in enumerate(ph) if p.name == "poll"]
    assert all(ph[i + 1].name != "poll" for i in polls[:-1])
    idle = [i for i, p in enumerate(ph) if p.name in ("poll", "wait")]
    last = 2 * c["batches_served"] + c["stalls"]
    ends = [ph[i + 1].round if i + 1 < len(ph) else last + 1 for i in idle]
    assert sum(e - ph[i].round for i, e in zip(idle, ends)) == (
        c["stalls"] + 1)
    # t0, t_exit and the exit round's read; ten per quantum; one per
    # round that launches and completes nothing
    assert clock.reads == 10 * c["batches_served"] + c["stalls"] + 3
    done = [e for e in trace.events if e.kind == "engine-counters"]
    assert done[-1].payload_dict()["launched_ahead"] == c["launched_ahead"]


def test_untraced_overlap_reads_the_clock_three_times_per_quantum(table):
    eng, _, clock = engine(table)
    eng.run(steady(), duration=0.04)
    c = eng.counters
    assert c["launched_ahead"] > 0 and c["stalls"] > 0
    assert clock.reads == 3 * c["batches_served"] + c["stalls"] + 3


class CheckedOutput:
    """Ready at its ``checks``-th readiness check, whatever the clock."""

    def __init__(self, checks: int):
        self.left = checks

    def is_ready(self) -> bool:
        self.left -= 1
        return self.left <= 0

    def block_until_ready(self):
        self.left = 0
        return self


def test_tracer_changes_no_overlapped_decision(table):
    def serve(tracer):
        class LaunchClock:  # time passes only at a launch
            t = 0.0

            def __call__(self):
                return self.t

        clock = LaunchClock()

        class Model(FakeModel):
            def execute(self, e, b):
                clock.t += 1e-3
                if self.phase_hook is not None:
                    self.phase_hook("launch")
                    self.phase_hook("wait")
                return CheckedOutput(3)

        sched = make_scheduler("edgeserving", table,
                               SchedulerConfig(slo=SLO, max_batch=4))
        picks = []
        decide = sched.decide

        def record(snapshot):
            d = decide(snapshot)
            picks.append((snapshot.now, d))
            return d

        sched.decide = record
        eng = ServingEngine([Model(None, table)], sched, clock=clock,
                            tracer=tracer)
        arrivals = [Request(req_id=i, model=0, arrival=i * 5e-4)
                    for i in range(40)]
        comps, t_exit = eng.run(arrivals, duration=0.0)
        return eng, picks, list(comps), t_exit

    off = serve(None)
    on = serve(Tracer())
    assert off[0].counters["launched_ahead"] > 0
    assert on[1] == off[1] and on[2] == off[2] and on[3] == off[3]
    assert on[0].counters == off[0].counters
