"""Telemetry subsystem: record-only tracing, timelines, and exporters.

The load-bearing guarantees, in order of importance:

  * **Observation is free of side effects** — a simulation with a
    :class:`Tracer` attached produces *bitwise-identical* metrics and
    decisions to the same simulation without one, on both engines. The
    tracer only appends to Python lists; it never touches the RNG, float
    accumulation order, or scheduler state. (Heisenberg clause.)
  * **Engines agree on the timeline, not just the aggregates** — the
    compiled scan engine reconstructs its decision/span timeline
    host-side from packed codes, and it must match the Python event
    loop record-for-record.
  * **Timelines conserve requests** — every arrival appears in exactly
    one span (completed / dropped / residual), including Symphony sheds
    and overload residuals.
  * **Rollups are consistent with the aggregates** — summing
    ``timeline_metrics`` bins reproduces ``ServingMetrics
    .violation_ratio`` exactly (same integer sums, same division).
  * **Exports round-trip** — NDJSON is lossless; Chrome trace JSON is
    strict (Perfetto rejects bare ``NaN``) with matched async ``b``/``e``
    request pairs; ``tools/tracestats.py`` summarizes both formats.
"""

from __future__ import annotations

import json
import math
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    ClusterSimulator,
    ProfileTable,
    Request,
    SchedulerConfig,
    ServingSimulator,
    SweepRunner,
    SweepSpec,
    Tracer,
    export_chrome_trace,
    export_ndjson,
    load_ndjson,
    make_dispatcher,
    make_fleet,
    make_scenario,
    make_scheduler,
    paper_rate_vector,
    poisson_arrivals,
    simulate_scan,
    timeline_metrics,
)

REPO = pathlib.Path(__file__).resolve().parent.parent
TRACESTATS = REPO / "tools" / "tracestats.py"

SCAN_POLICIES = ("edgeserving", "edgeserving-lattice",
                 "allfinal-deadline-aware")


@pytest.fixture(scope="module")
def table():
    return ProfileTable.paper_rtx3080()


def _arrivals(lam=110.0, horizon=2.0, seed=7):
    return poisson_arrivals(paper_rate_vector(lam), horizon, seed=seed)


def _run(policy, table, arrivals, horizon, tracer=None, seed=7, slo=0.05,
         warmup=20):
    sched = make_scheduler(policy, table, SchedulerConfig(slo=slo))
    sim = ServingSimulator(sched, table, num_models=3, seed=seed,
                           tracer=tracer)
    return sim.run(list(arrivals), horizon, warmup_tasks=warmup)


def _assert_span_conservation(trace, n_arrivals):
    counts = trace.span_counts()
    assert sum(counts.values()) == n_arrivals
    ids = [s.req_id for s in trace.spans]
    assert len(ids) == len(set(ids))  # each request exactly once


def _assert_decisions_equal(a, b):
    assert len(a) == len(b)
    for ra, rb in zip(a, b):
        assert (ra.t, ra.t_end, ra.device, ra.model, ra.exit_idx,
                ra.batch_size) == (rb.t, rb.t_end, rb.device, rb.model,
                                   rb.exit_idx, rb.batch_size)
        assert ra.queue_depths == rb.queue_depths
        assert ra.oldest_ages == rb.oldest_ages
        # scores travel through float32 on the scan path; they may differ
        # at the ulp level between engines, where no rtol passes a zero
        np.testing.assert_allclose(ra.score, rb.score, rtol=1e-6,
                                   atol=1e-12)
        if math.isfinite(ra.margin) or math.isfinite(rb.margin):
            np.testing.assert_allclose(ra.margin, rb.margin, rtol=1e-5,
                                       atol=1e-12)


class TestHeisenberg:
    """Tracing on == tracing off, bitwise, on every engine."""

    @given(seed=st.integers(0, 999),
           lam=st.sampled_from([60.0, 130.0, 200.0]),
           policy=st.sampled_from(("edgeserving", "symphony",
                                   "earlyexit-edf", "all-final")))
    @settings(max_examples=6, deadline=None)
    def test_python_engine_bitwise(self, table, seed, lam, policy):
        arrivals = _arrivals(lam, 1.5, seed)
        off = _run(policy, table, arrivals, 1.5, seed=seed)
        on = _run(policy, table, arrivals, 1.5, tracer=Tracer(), seed=seed)
        assert off.metrics == on.metrics
        assert off.trace is None
        _assert_span_conservation(on.trace, len(arrivals))

    @given(seed=st.integers(0, 999),
           policy=st.sampled_from(SCAN_POLICIES))
    @settings(max_examples=4, deadline=None)
    def test_scan_engine_bitwise(self, table, seed, policy):
        arrivals = _arrivals(110.0, 1.5, seed)
        sched = make_scheduler(policy, table, SchedulerConfig(slo=0.05))
        off = simulate_scan(sched, table, list(arrivals), 1.5, num_models=3,
                            warmup_tasks=20)
        on = simulate_scan(sched, table, list(arrivals), 1.5, num_models=3,
                           warmup_tasks=20, tracer=Tracer())
        assert off.metrics == on.metrics
        assert off.trace is None
        _assert_span_conservation(on.trace, len(arrivals))

    def test_rerun_resets_the_tracer(self, table):
        tracer = Tracer()
        arrivals = _arrivals()
        a = _run("edgeserving", table, arrivals, 2.0, tracer=tracer)
        b = _run("edgeserving", table, arrivals, 2.0, tracer=tracer)
        assert a.metrics == b.metrics
        assert len(a.trace.decisions) == len(b.trace.decisions)
        assert len(a.trace.spans) == len(b.trace.spans)


class TestEngineTimelineEquivalence:
    """Python event loop ≡ compiled scan, record-for-record."""

    @given(seed=st.integers(0, 999),
           lam=st.sampled_from([60.0, 130.0, 200.0]),
           policy=st.sampled_from(SCAN_POLICIES))
    @settings(max_examples=6, deadline=None)
    def test_property_same_timeline(self, table, seed, lam, policy):
        arrivals = _arrivals(lam, 1.5, seed)
        py = _run(policy, table, arrivals, 1.5, tracer=Tracer(), seed=seed)
        sched = make_scheduler(policy, table, SchedulerConfig(slo=0.05))
        sc = simulate_scan(sched, table, list(arrivals), 1.5, num_models=3,
                           warmup_tasks=20, tracer=Tracer())
        _assert_decisions_equal(py.trace.decisions, sc.trace.decisions)
        # completed spans finish in the same order on both engines
        pyc = [s for s in py.trace.spans if s.status == "completed"]
        scc = [s for s in sc.trace.spans if s.status == "completed"]
        assert pyc == scc
        pyr = sorted(s.req_id for s in py.trace.spans
                     if s.status == "residual")
        scr = sorted(s.req_id for s in sc.trace.spans
                     if s.status == "residual")
        assert pyr == scr
        assert py.trace.meta["engine"] == "python"
        assert sc.trace.meta["engine"] == "scan"

    def test_scan_margin_matches_rescored_python(self, table):
        """The scan step computes the margin inside the compiled kernel;
        the Python engine re-scores host-side. Overload makes margins
        finite and discriminating."""
        arrivals = _arrivals(200.0, 2.0)
        py = _run("edgeserving", table, arrivals, 2.0, tracer=Tracer())
        sched = make_scheduler("edgeserving", table,
                               SchedulerConfig(slo=0.05))
        sc = simulate_scan(sched, table, list(arrivals), 2.0, num_models=3,
                           warmup_tasks=20, tracer=Tracer())
        margins_py = [r.margin for r in py.trace.decisions]
        margins_sc = [r.margin for r in sc.trace.decisions]
        assert any(math.isfinite(m) for m in margins_py)
        for a, b in zip(margins_py, margins_sc):
            if math.isfinite(a) or math.isfinite(b):
                np.testing.assert_allclose(a, b, rtol=1e-5)


class TestSpanConservation:
    def test_symphony_sheds_are_dropped_spans(self, table):
        arrivals = _arrivals(220.0, 2.0)
        res = _run("symphony", table, arrivals, 2.0, tracer=Tracer())
        counts = res.trace.span_counts()
        assert counts.get("dropped", 0) == res.metrics.dropped > 0
        _assert_span_conservation(res.trace, len(arrivals))
        assert any(e.kind == "shed" for e in res.trace.events)

    def test_overload_residuals_are_residual_spans(self, table):
        # all-final at high load leaves work queued at the drain cap
        arrivals = _arrivals(240.0, 2.0)
        sched = make_scheduler("all-final", table, SchedulerConfig(slo=0.05))
        sim = ServingSimulator(sched, table, num_models=3, seed=7,
                               tracer=Tracer(), drain_cap=0.1)
        res = sim.run(list(arrivals), 2.0, warmup_tasks=20)
        counts = res.trace.span_counts()
        assert counts.get("residual", 0) == res.metrics.residual_queue > 0
        _assert_span_conservation(res.trace, len(arrivals))
        # residuals in single-device engines carry the device=-1 sentinel
        assert all(s.device == -1 for s in res.trace.spans
                   if s.status == "residual")

    def test_slack_sign_matches_violation_count(self, table):
        arrivals = _arrivals(200.0, 2.0)
        res = _run("edgeserving", table, arrivals, 2.0, tracer=Tracer())
        comp = sorted((s for s in res.trace.spans
                       if s.status == "completed"),
                      key=lambda s: s.finish)
        comp = comp[res.metrics.warmup_used:]
        late = sum(1 for s in comp if s.slack < 0)
        # Eq. 2 accounting: (late + dropped) / (done + dropped)
        expect = ((late + res.metrics.dropped)
                  / (len(comp) + res.metrics.dropped))
        assert expect == pytest.approx(res.metrics.violation_ratio, abs=1e-12)


class TestClusterTelemetry:
    def test_g1_cluster_matches_single_device_timeline(self, table):
        arrivals = _arrivals()
        single = _run("edgeserving", table, arrivals, 2.0, tracer=Tracer())
        sim = ClusterSimulator(
            make_fleet("homogeneous", 1, table), policy="edgeserving",
            config=SchedulerConfig(slo=0.05),
            dispatcher=make_dispatcher("least-loaded", slo=0.05),
            num_models=3, seed=7, tracer=Tracer())
        clus = sim.run(list(arrivals), 2.0, warmup_tasks=20)
        _assert_decisions_equal(single.trace.decisions,
                                clus.trace.decisions)
        assert clus.trace.meta["engine"] == "cluster"
        _assert_span_conservation(clus.trace, len(arrivals))

    def test_failure_emits_events_and_conserves_spans(self, table):
        arrivals = _arrivals(150.0, 2.0)
        sim = ClusterSimulator(
            make_fleet("homogeneous", 2, table, fail_at=((1, 0.8),)),
            policy="edgeserving", config=SchedulerConfig(slo=0.05),
            dispatcher=make_dispatcher("least-loaded", slo=0.05),
            num_models=3, seed=7, tracer=Tracer())
        res = sim.run(list(arrivals), 2.0, warmup_tasks=20)
        kinds = {e.kind for e in res.trace.events}
        assert "device-failure" in kinds
        assert "failover" in kinds
        fail = next(e for e in res.trace.events
                    if e.kind == "device-failure")
        assert fail.device == 1
        assert fail.t == pytest.approx(0.8)
        _assert_span_conservation(res.trace, len(arrivals))
        assert res.trace.meta["num_devices"] == 2
        assert {r.device for r in res.trace.decisions} <= {0, 1}


class TestTimelineMetrics:
    @given(seed=st.integers(0, 999), num_bins=st.integers(1, 60),
           policy=st.sampled_from(("edgeserving", "symphony", "all-final")))
    @settings(max_examples=8, deadline=None)
    def test_bins_sum_back_to_aggregate_exactly(self, table, seed, num_bins,
                                                policy):
        arrivals = _arrivals(180.0, 1.5, seed)
        res = _run(policy, table, arrivals, 1.5, tracer=Tracer(), seed=seed)
        tm = timeline_metrics(res.trace, num_bins=num_bins)
        # exact: identical integer sums, identical float division
        assert tm.aggregate_violation_ratio() == res.metrics.violation_ratio
        assert int(tm.dropped.sum()) == res.metrics.dropped

    def test_flash_crowd_spike_is_localized(self, table):
        proc = make_scenario("flash-crowd", paper_rate_vector(160.0),
                             spike_start=2.0, spike_duration=0.5,
                             magnitude=5.0)
        arrivals = proc.generate(5.0, seed=7)
        res = _run("edgeserving", table, arrivals, 5.0, tracer=Tracer(),
                   warmup=100)
        tm = timeline_metrics(res.trace, num_bins=20, t_end=5.0)
        qd = np.nan_to_num(tm.queue_depth)
        spike_bins = range(8, 12)  # spike window [2.0, 2.5) plus drain
        assert qd[list(spike_bins)].max() > 3 * qd[:8].max()
        # Eq. 6 anatomy: exit depth shifts down inside the spike
        depth = np.nan_to_num(tm.mean_exit_depth, nan=np.inf)
        assert depth[8:11].min() < np.nanmean(tm.mean_exit_depth[:8])
        assert tm.num_bins == 20
        assert tm.edges[0] == 0.0 and tm.edges[-1] == 5.0

    def test_utilization_bounded_by_device_count(self, table):
        arrivals = _arrivals(200.0, 2.0)
        res = _run("edgeserving", table, arrivals, 2.0, tracer=Tracer())
        tm = timeline_metrics(res.trace, num_bins=10)
        assert np.all(tm.utilization >= 0.0)
        assert np.all(tm.utilization <= 1.0 + 1e-9)


class TestExporters:
    @pytest.fixture(scope="class")
    def traced(self, table):
        arrivals = _arrivals(220.0, 2.0)
        # symphony: gives the trace drops + shed events + NaN margins,
        # the fields most likely to break strict JSON
        return _run("symphony", table, arrivals, 2.0, tracer=Tracer())

    def test_ndjson_round_trips_losslessly(self, traced, tmp_path):
        path = str(tmp_path / "t.ndjson")
        export_ndjson(traced.trace, path)
        back = load_ndjson(path)
        # NaN != NaN blocks plain dataclass equality (symphony traces carry
        # NaN margins); losslessness == a second export is byte-identical.
        path2 = str(tmp_path / "t2.ndjson")
        export_ndjson(back, path2)
        assert open(path).read() == open(path2).read()
        assert len(back.decisions) == len(traced.trace.decisions)
        assert len(back.spans) == len(traced.trace.spans)
        completed = [s for s in back.spans if s.status == "completed"]
        assert completed == [s for s in traced.trace.spans
                             if s.status == "completed"]
        assert back.meta == traced.trace.meta
        nan_margins = [r.margin for r in back.decisions
                       if not math.isfinite(r.margin)]
        assert nan_margins and all(math.isnan(m) for m in nan_margins)

    def test_chrome_trace_is_strict_perfetto_json(self, traced, tmp_path):
        path = str(tmp_path / "t.chrome.json")
        export_chrome_trace(traced.trace, path)

        def reject(s):
            raise AssertionError(f"non-strict JSON constant {s!r}")

        doc = json.load(open(path), parse_constant=reject)
        evs = doc["traceEvents"]
        assert {"displayTimeUnit", "otherData"} <= set(doc)
        for e in evs:
            assert e["ph"] in ("M", "X", "i", "b", "e")
            assert isinstance(e["ts"], (int, float))
            if e["ph"] == "X":
                assert e["dur"] >= 0.0
        # async request spans pair up exactly
        opens = [e["id"] for e in evs
                 if e["ph"] == "b" and e.get("cat") == "request"]
        closes = [e["id"] for e in evs
                  if e["ph"] == "e" and e.get("cat") == "request"]
        assert sorted(opens) == sorted(closes)
        assert len(opens) == len(set(opens))
        n_quanta = sum(1 for e in evs if e["ph"] == "X")
        assert n_quanta == len(traced.trace.decisions)

    @pytest.mark.parametrize("fmt", ["ndjson", "chrome"])
    def test_tracestats_summarizes_both_formats(self, traced, tmp_path, fmt):
        if fmt == "ndjson":
            path = str(tmp_path / "t.ndjson")
            export_ndjson(traced.trace, path)
        else:
            path = str(tmp_path / "t.chrome.json")
            export_chrome_trace(traced.trace, path)
        out = subprocess.run(
            [sys.executable, str(TRACESTATS), path, "--top", "3",
             "--bins", "5"],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "PYTHONPATH": str(REPO / "src")})
        assert out.returncode == 0, out.stderr
        assert "per-model decisions" in out.stdout
        assert "worst 3 requests" in out.stdout
        assert f"dropped={traced.metrics.dropped}" in out.stdout

    def test_tracestats_rejects_empty_trace(self, tmp_path):
        path = tmp_path / "empty.ndjson"
        path.write_text('{"type": "meta", "engine": "python"}\n')
        out = subprocess.run(
            [sys.executable, str(TRACESTATS), str(path)],
            capture_output=True, text=True, timeout=60)
        assert out.returncode != 0

    def test_tracestats_rejects_unmatched_pairs(self, tmp_path):
        doc = {"traceEvents": [
            {"ph": "b", "pid": 2, "tid": 0, "cat": "request", "id": "0x1",
             "name": "m0", "ts": 0.0,
             "args": {"req": 1, "model": 0, "status": "completed",
                      "deadline_ms": 50.0, "slack_ms": 1.0, "exit": 0,
                      "batch": 1}},
        ]}
        path = tmp_path / "broken.chrome.json"
        path.write_text(json.dumps(doc))
        out = subprocess.run(
            [sys.executable, str(TRACESTATS), str(path)],
            capture_output=True, text=True, timeout=60)
        assert out.returncode != 0
        assert "unclosed" in out.stderr


class TestSweepSurface:
    def test_trace_flag_attaches_and_defaults_off(self, table):
        runner = SweepRunner(table)
        base = dict(policy="edgeserving", rate=110.0, seed=7, horizon=1.5,
                    warmup_tasks=20)
        off = runner.run_cell(SweepSpec(**base))
        on = runner.run_cell(SweepSpec(**base, trace=True))
        assert off.trace is None
        assert on.trace is not None
        assert off.metrics == on.metrics
        assert len(on.trace.decisions) > 0

    def test_trace_flag_on_scan_engine(self, table):
        runner = SweepRunner(table)
        base = dict(policy="edgeserving", rate=110.0, seed=7, horizon=1.5,
                    warmup_tasks=20, engine="scan")
        off = runner.run_cell(SweepSpec(**base))
        on = runner.run_cell(SweepSpec(**base, trace=True))
        assert off.trace is None
        assert on.trace.meta["engine"] == "scan"
        assert off.metrics == on.metrics


class StepClock:
    """Advances 1 ms on every read, and counts the reads."""

    def __init__(self):
        self.t = 0.0
        self.reads = 0

    def __call__(self):
        self.reads += 1
        self.t += 1e-3
        return self.t


def _live_engine(table, tracer=None, clock=None, data_fn=None):
    """A live engine over one fake model (two exits, B <= 4)."""
    from repro.runtime.server import ServedModel, ServingEngine

    view = table.select_models([0]).restrict_exits([0, 3])
    mod = ServedModel("m0", values=None,
                      forward_fn=lambda v, x, e: np.sum(x),
                      data_fn=data_fn or (lambda b: np.ones((b, 2))),
                      num_exits=2)
    sched = make_scheduler("edgeserving", view,
                           SchedulerConfig(slo=0.05, max_batch=4))
    return ServingEngine([mod], sched, clock=clock or StepClock(),
                         tracer=tracer), view


class TestEngineCounters:
    """Live engine: structured counters + trace through the same tracer."""

    def test_counters_reconcile_with_completions(self, table):
        tracer = Tracer()
        eng, view = _live_engine(table, tracer)
        arrivals = [Request(req_id=i, model=0, arrival=0.0)
                    for i in range(24)]
        comps, span = eng.run(arrivals, duration=0.05)
        c = eng.counters
        assert c["requests_served"] == len(comps) == 24
        assert 0 < c["batches_served"] <= 24
        assert c["dropped"] == 0
        assert c["drain_residual"] == 0
        trace = eng.trace(run="unit")
        assert trace.meta["engine"] == "live"
        assert trace.meta["run"] == "unit"
        assert len(trace.decisions) == c["batches_served"]
        done = [e for e in trace.events if e.kind == "engine-counters"]
        assert done and done[-1].payload_dict()["requests_served"] == 24

    def test_counters_without_tracer_still_populate(self, table):
        eng, _ = _live_engine(table, tracer=None)
        arrivals = [Request(req_id=i, model=0, arrival=0.0)
                    for i in range(8)]
        comps, _ = eng.run(arrivals, duration=0.05)
        assert eng.counters["requests_served"] == len(comps)
        assert eng.trace() is None


DISPATCH_PHASES = ["ingest", "snapshot", "prune", "decide", "pop", "input",
                   "launch", "wait", "record", "trace"]


class TestEnginePhases:
    """Live engine: the host loop's phase spans."""

    def _traced(self, table):
        eng, _ = _live_engine(table, Tracer())
        # a burst, a quiet stretch of idle rounds, another burst
        arrivals = [Request(req_id=i, model=0, arrival=0.0 if i < 12
                            else 0.06) for i in range(24)]
        _, t_exit = eng.run(arrivals, duration=0.08)
        return eng, eng.trace(), t_exit

    def test_phase_spans_tile_the_run(self, table):
        _, trace, t_exit = self._traced(table)
        ph = trace.phases
        assert ph[0].start == 0.0 and ph[-1].end == t_exit
        for a, b in zip(ph, ph[1:]):
            assert a.end == b.start
        assert all(p.end >= p.start for p in ph)

    def test_dispatching_rounds_run_nine_phases_in_order(self, table):
        # each quantum's nine loop phases, then the tracer's own ``trace``:
        # the launching round's seven, the quantum's ``wait`` (the rounds
        # that launch and complete nothing until it is seen done), then the
        # completing round's ``record`` and ``trace``. Another quantum's
        # phases may fall between its ``launch`` and its ``wait`` when it
        # was launched while this one ran.
        eng, trace, _ = self._traced(table)
        quanta, rounds = {}, {}
        for p in trace.phases:
            if p.quantum >= 0:
                quanta.setdefault(p.quantum, []).append(p.name)
                rounds.setdefault(p.quantum, []).append(p.round)
        assert list(quanta) == list(range(len(trace.decisions)))
        assert len(quanta) == eng.counters["batches_served"]
        seen = set()
        for q, names in quanta.items():
            r = trace.decisions[q]
            miss = (r.exit_idx, r.batch_size) not in seen
            seen.add((r.exit_idx, r.batch_size))
            want = list(DISPATCH_PHASES)
            if miss:  # the executable is built on first use: compile
                want.insert(want.index("launch"), "compile")
            assert names == want
            at = dict(zip(names, rounds[q]))
            launching = rounds[q][:names.index("wait")]
            assert set(launching) == {at["ingest"]}
            assert at["ingest"] < at["wait"] <= at["record"] == at["trace"]

    def test_consecutive_idle_rounds_make_one_poll(self, table):
        eng, trace, _ = self._traced(table)
        ph = trace.phases
        polls = [i for i, p in enumerate(ph) if p.name == "poll"]
        assert len(polls) >= 2          # the quiet stretch and the exit
        assert all(ph[i].quantum == -1 for i in polls)
        assert all(ph[i + 1].name != "poll" for i in polls[:-1])
        # each poll or wait covers the rounds from its own up to the next
        # span's; the last poll runs to the exit round, so together they
        # cover every round that launched and completed nothing, and it
        c = eng.counters
        last = 2 * c["batches_served"] + c["stalls"]
        idle = [i for i, p in enumerate(ph) if p.name in ("poll", "wait")]
        ends = [ph[i + 1].round if i + 1 < len(ph) else last + 1
                for i in idle]
        covered = {i: e - ph[i].round for i, e in zip(idle, ends)}
        assert max(covered[i] for i in polls) >= 2
        assert sum(covered.values()) == c["stalls"] + 1

    def test_untraced_loop_reads_the_clock_three_times_per_quantum(
            self, table):
        clock = StepClock()
        eng, _ = _live_engine(table, clock=clock)
        arrivals = [Request(req_id=i, model=0, arrival=0.0 if i < 12
                            else 0.03) for i in range(24)]
        eng.run(arrivals, duration=0.05)
        c = eng.counters
        assert c["stalls"] > 0
        # t0, t_exit and the exit round's read, then three per
        # dispatching round and one per idle round
        assert clock.reads == 3 * c["batches_served"] + c["stalls"] + 3

    def test_traced_loop_reads_the_clock_once_per_boundary(self, table):
        clock = StepClock()
        eng, _ = _live_engine(table, Tracer(), clock)
        arrivals = [Request(req_id=i, model=0, arrival=0.0 if i < 12
                            else 0.2) for i in range(24)]
        eng.run(arrivals, duration=0.25)
        c = eng.counters
        assert c["stalls"] > 0
        compiles = len(eng.models[0].executables)
        # t0, t_exit and the exit round's read; ten per dispatching round
        # and one per compile; an idle round (every queue empty) reads once
        assert clock.reads == (10 * c["batches_served"] + compiles
                               + c["stalls"] + 3)

    def test_tracer_records_fall_in_their_own_phase(self, table):
        clock = StepClock()

        class SlowTracer(Tracer):  # each decision record takes a second
            def record_decision(self, *args, **kwargs):
                clock.t += 1.0
                super().record_decision(*args, **kwargs)

        eng, _ = _live_engine(table, SlowTracer(), clock)
        arrivals = [Request(req_id=i, model=0, arrival=0.0)
                    for i in range(8)]
        eng.run(arrivals, duration=0.0)
        ph = eng.trace().phases
        traced = [p for p in ph if p.name == "trace"]
        assert len(traced) == eng.counters["batches_served"] > 0
        assert all(p.end - p.start >= 1.0 for p in traced)
        assert all(p.end - p.start < 1.0 for p in ph if p.name != "trace")

    def test_tracer_changes_no_decision(self, table):
        def serve(tracer):
            class ExecuteClock:  # time passes only inside execute
                t = 0.0

                def __call__(self):
                    return self.t

            clock = ExecuteClock()

            def data_fn(b):
                clock.t += 1e-3
                return np.ones((b, 2))

            eng, _ = _live_engine(table, tracer, clock, data_fn)
            picks = []
            decide = eng.scheduler.decide

            def record(snapshot):
                d = decide(snapshot)
                picks.append(d)
                return d

            eng.scheduler.decide = record
            arrivals = [Request(req_id=i, model=0, arrival=i * 5e-4)
                        for i in range(40)]
            comps, t_exit = eng.run(arrivals, duration=0.0)
            return eng, picks, list(comps), t_exit

        off = serve(None)
        on = serve(Tracer())
        assert on[1] == off[1] and on[2] == off[2] and on[3] == off[3]
        assert on[0].counters == off[0].counters
        assert len(on[0].trace().decisions) == off[0].counters[
            "batches_served"]

    def test_live_margins_are_nan(self, table):
        _, trace, _ = self._traced(table)
        assert trace.decisions
        assert all(math.isnan(r.margin) for r in trace.decisions)

    def test_phases_export_and_round_trip(self, table, tmp_path):
        _, trace, _ = self._traced(table)
        path = str(tmp_path / "live.ndjson")
        export_ndjson(trace, path)
        assert load_ndjson(path).phases == trace.phases
        chrome = str(tmp_path / "live.chrome.json")
        export_chrome_trace(trace, chrome)
        evs = json.load(open(chrome))["traceEvents"]
        host = [e for e in evs if e["ph"] == "M"
                and e["args"].get("name") == "host loop"]
        assert len(host) == 1
        drawn = [e for e in evs if e.get("cat") == "phase"]
        assert [e["name"] for e in drawn] == [p.name for p in trace.phases]
        assert {e["tid"] for e in drawn} == {host[0]["tid"]}
