"""The reduction of the live engine's phase spans, on small synthetic
spans and device intervals with answers computed by hand."""

import numpy as np
import pytest

from bench import phases, reduce
from bench.tests.test_bench_reduce import make_run
from bench.trace import DeviceTrace

# Two dispatching rounds and an idle stretch that tile [0, 1] s; quantum 0
# compiles; each round ends with the tracer's own ``trace`` span.
SPANS = [
    (0, 0, "ingest", 0.0, 0.01), (0, 0, "snapshot", 0.01, 0.02),
    (0, 0, "prune", 0.02, 0.03), (0, 0, "decide", 0.03, 0.1),
    (0, 0, "pop", 0.1, 0.11), (0, 0, "input", 0.11, 0.12),
    (0, 0, "compile", 0.12, 0.2), (0, 0, "launch", 0.2, 0.25),
    (0, 0, "wait", 0.25, 0.4), (0, 0, "record", 0.4, 0.43),
    (0, 0, "trace", 0.43, 0.45),
    (1, -1, "poll", 0.45, 0.6),
    (3, 1, "ingest", 0.6, 0.61), (3, 1, "snapshot", 0.61, 0.62),
    (3, 1, "prune", 0.62, 0.63), (3, 1, "decide", 0.63, 0.66),
    (3, 1, "pop", 0.66, 0.67), (3, 1, "input", 0.67, 0.68),
    (3, 1, "launch", 0.68, 0.7), (3, 1, "wait", 0.7, 0.92),
    (3, 1, "record", 0.92, 0.97), (3, 1, "trace", 0.97, 1.0),
]
# The quantum annotations start 1 and 3 ms after their launch (quanta
# listed out of order): the offset is their median, 2 ms.
QUANTUM_K = np.array([1, 0])
QUANTUM_START = np.array([0.683, 0.201])


def phase_run(window=(0.0, 1.0), device=None):
    # request 0 is served by quantum 0, request 1 (due at 0.5) by quantum 1
    return make_run([0.0, 0.5], [0.11, 0.67], [0.4, 0.92], window=window,
                    device=device)


def device_on_decide_clock(offset=0.002):
    """Ops at [0.26, 0.38] and [0.72, 0.85] s on the engine's clock,
    ``offset`` later on the ``decide#`` clock."""
    start, end = np.array([0.26, 0.72]) + offset, np.array([0.38, 0.85])
    return DeviceTrace(["x", "x"], start, end + offset, start, end + offset,
                       [])


def test_tiling_fixture_has_no_gap_or_overlap():
    ends = np.array([s[4] for s in SPANS])
    starts = np.array([s[3] for s in SPANS])
    assert starts[0] == 0.0 and ends[-1] == 1.0
    assert np.array_equal(starts[1:], ends[:-1])


@pytest.mark.parametrize("window,loop,decide,launch,tracer", [
    # loop: (0.01 * 4 + 0.03) and (0.01 * 4 + 0.05) s a round, without
    # the tracer's ``trace`` spans (0.02 and 0.03 s)
    ((0.0, 1.0), 0.08e6, 0.05e6, 0.045e6, 0.025e6),
    ((0.5, 1.0), 0.09e6, 0.03e6, 0.03e6, 0.03e6),  # round 3 alone
    # the window ends inside round 3's decide (as when the profiler stops
    # there): only spans wholly inside it count
    ((0.0, 0.65), 0.07e6, 0.07e6, 0.06e6, 0.02e6),
])
def test_phase_span_metrics(window, loop, decide, launch, tracer):
    run = phase_run(window)
    assert phases.loop_overhead_us(run, SPANS) == pytest.approx(loop)
    assert phases.round_mean_us(run, SPANS, ("trace",)) == pytest.approx(
        tracer)
    assert phases.decide_span_us(run, SPANS) == pytest.approx(decide)
    assert phases.launch_us(run, SPANS) == pytest.approx(launch)


def test_no_phase_spans_read_nothing():
    run = make_run([0.0], [0.0], [0.01], window=(0.0, 1.0),
                   device=device_on_decide_clock())
    assert phases.loop_overhead_us(run, []) is None
    assert phases.decide_span_us(run, []) is None
    assert phases.launch_us(run, []) is None
    assert phases.longest_spans([]) is None
    assert phases.quantum_offset([], QUANTUM_K, QUANTUM_START) is None
    assert phases.host_idle_pct(run, [], 0.002) is None
    assert phases.host_idle_by_phase(run, [], 0.002) is None
    assert phases.wait_idle(run, [], 0.002) is None


def test_longest_spans_over_the_whole_run():
    longest = phases.longest_spans(SPANS)
    assert [n for n, _, _ in longest] == ["wait", "wait", "poll", "compile",
                                          "decide"]
    assert longest[0][1:] == pytest.approx([0.7, 0.22])


def test_quantum_offset_is_the_median_over_quanta():
    assert phases.quantum_offset(SPANS, QUANTUM_K,
                                 QUANTUM_START) == pytest.approx(0.002)
    # a trace without quantum annotations cannot be aligned
    assert phases.quantum_offset(SPANS, QUANTUM_K[:0],
                                 QUANTUM_START[:0]) is None
    run = phase_run(device=device_on_decide_clock())
    assert phases.host_idle_pct(run, SPANS, None) is None


def test_host_idle_counts_idle_while_a_request_waits():
    run = phase_run(device=device_on_decide_clock())
    offset = phases.quantum_offset(SPANS, QUANTUM_K, QUANTUM_START)
    # idle [0, .26] [.38, .72] [.85, 1]; waiting [0, .4] [.5, .92]
    # -> [0, .26] [.38, .4] [.5, .72] [.85, .92] = 0.57 s, of which 0.12 s
    # falls in the programs' ``wait``: the host caused 0.45 s
    assert phases.host_idle_pct(run, SPANS, offset) == pytest.approx(45.0)
    # the device idles 75% of the window: the host's share is part of it
    assert reduce.device_idle_pct(run) == pytest.approx(75.0)
    by_phase = dict(phases.host_idle_by_phase(run, SPANS, offset))
    assert by_phase == pytest.approx({
        "ingest": 0.02, "snapshot": 0.02, "prune": 0.02, "decide": 0.10,
        "pop": 0.02, "input": 0.02, "compile": 0.08, "launch": 0.07,
        "poll": 0.10})
    assert sum(by_phase.values()) == pytest.approx(0.45)
    assert phases.wait_idle(run, SPANS, offset) == [
        ["wait", pytest.approx(0.12)]]
    shares = [d for _, d in phases.host_idle_by_phase(run, SPANS, offset)]
    assert shares == sorted(shares, reverse=True)


def test_intersect_of_interval_sets():
    x = np.array([[0.0, 1.0], [2.0, 3.0], [4.0, 6.0]])
    y = np.array([[0.5, 2.5], [5.0, 7.0]])
    assert phases.intersect(x, y).tolist() == [[0.5, 1.0], [2.0, 2.5],
                                               [5.0, 6.0]]
    assert phases.intersect(x, y[:0]).shape == (0, 2)
