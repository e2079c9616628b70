"""The reduction from a run's records to metrics, on small synthetic runs."""

import numpy as np
import pytest

from bench import correctness, reduce
from bench.harness import Capture
from bench.trace import DeviceTrace

NAN = float("nan")


def make_run(arrival, dispatch, finish, exit_idx=None, batch=None,
             model=None, seconds=1.0, tau=0.05, **kw):
    n = len(arrival)
    finish = np.asarray(finish, dtype=float)
    return reduce.Run(
        seconds=seconds, tau=tau,
        arrival=np.asarray(arrival, dtype=float),
        model=np.zeros(n, int) if model is None else np.asarray(model),
        dispatch=np.asarray(dispatch, dtype=float), finish=finish,
        exit_idx=(np.where(np.isfinite(finish), 3, -1) if exit_idx is None
                  else np.asarray(exit_idx)),
        batch=(np.where(np.isfinite(finish), 1, 0) if batch is None
               else np.asarray(batch)), **kw)


def test_p95_is_over_due_times_of_completed_requests():
    # latencies 1..20 ms from due time; one stranded request is left out
    arrival = np.arange(21) * 0.01
    lat = np.append(np.arange(1, 21) * 1e-3, NAN)
    run = make_run(arrival, arrival + lat / 2, arrival + lat)
    assert reduce.p95_ms(run) == pytest.approx(
        np.percentile(np.arange(1, 21), 95))


def test_attainment_counts_stranded_and_late_as_misses():
    arrival = [0.0, 0.1, 0.2, 0.3]
    finish = [0.01, 0.2, NAN, 0.35]        # met, late, stranded, met
    run = make_run(arrival, arrival, finish)
    assert reduce.attainment_pct(run) == pytest.approx(50.0)


def test_served_rps_counts_completions_inside_the_window():
    run = make_run([0.0, 0.5, 0.9], [0.0, 0.5, 0.9], [0.1, 0.6, 1.02],
                   seconds=1.0)
    assert reduce.served_rps(run) == pytest.approx(2.0)


def test_mean_exit_depth_is_exit_index_plus_one():
    run = make_run([0, 0.1, 0.2], [0, 0.1, 0.2], [0.01, 0.11, NAN],
                   exit_idx=[0, 3, -1])
    assert reduce.mean_exit_depth(run) == pytest.approx(2.5)


def test_loop_gap_only_while_a_request_waits():
    # quantum 1: [0.00, 0.01]; request 2 arrived at 0.005, so the gap to
    # quantum 2 at 0.012 counts (2 ms). Quantum 2 ends at 0.02 with the
    # queue empty until 0.1: that gap does not count.
    arrival = [0.0, 0.005, 0.1]
    dispatch = [0.0, 0.012, 0.1]
    finish = [0.01, 0.02, 0.11]
    run = make_run(arrival, dispatch, finish)
    assert reduce.loop_gap_us(run) == pytest.approx(2000.0)


def test_queue_wait_in_window():
    run = make_run([0.0, 0.5], [0.01, 0.53], [0.02, 0.54],
                   window=(0.4, 0.6))
    assert reduce.queue_wait_ms(run) == pytest.approx(30.0)


def test_decide_us_averages_calls_that_dispatched_in_the_window():
    log = np.array([[0.1, 10e-6, 1], [0.2, 99e-6, 0], [0.3, 30e-6, 1],
                    [0.9, 50e-6, 1]])
    run = make_run([0.0], [0.0], [0.01], decide=log, window=(0.0, 0.5))
    assert reduce.decide_us(run) == pytest.approx(20.0)


def test_quantum_over_profile_is_p95_of_ratio():
    table = np.full((1, 4, 2), 0.002)
    arrival = np.arange(20) * 0.01
    run = make_run(arrival, arrival, arrival + 0.004, latency_table=table,
                   batch_sizes=(1, 2))
    assert reduce.quantum_over_profile(run) == pytest.approx(2.0)


def test_idle_share_is_one_minus_union():
    start = np.array([0.0, 0.05, 0.5, 0.9])
    end = np.array([0.1, 0.2, 0.6, 1.5])   # overlap, and one past the end
    dev = DeviceTrace(["a", "b", "a", "c"], start, end, start[:0], end[:0],
                      [])
    run = make_run([0.0], [0.0], [0.01], device=dev, window=(0.0, 1.0))
    # busy: [0, 0.2] + [0.5, 0.6] + [0.9, 1.0] = 0.4 s
    assert reduce.busy_s(run) == pytest.approx(0.4)
    assert reduce.device_idle_pct(run) == pytest.approx(60.0)
    bd = reduce.breakdown(run)
    assert bd["device_ops"][0][0] == "a"
    assert [g[1] for g in bd["idle_gaps"]] == pytest.approx([0.3, 0.3])


def test_no_device_trace_reads_nothing():
    run = make_run([0.0], [0.0], [0.01], window=(0.0, 1.0))
    assert reduce.device_idle_pct(run) is None
    assert reduce.step_mfu_pct(run) is None
    assert reduce.breakdown(run) is None


def test_step_mfu_matches_launches_to_quanta():
    # two quanta of 1e9 FLOPs, each with one 10 us launch; a launch outside
    # every quantum is not counted
    arrival = [0.0, 0.1]
    dispatch = [0.0, 0.1]
    finish = [0.001, 0.101]
    mod_start = np.array([0.0002, 0.1002, 0.5])
    dev = DeviceTrace([], mod_start, mod_start + 1e-5, mod_start,
                      mod_start + 1e-5, [])
    run = make_run(arrival, dispatch, finish, device=dev, window=(0.0, 1.0),
                   flops=lambda m, e, b: 1e9, device_kind="TPU v5 lite")
    want = 2e9 / (2e-5 * 197e12) * 100
    assert reduce.step_mfu_pct(run) == pytest.approx(want)


def test_idle_gaps_are_named_by_host_activity():
    # device busy [0, 0.1] and [0.3, 0.4]; decide covers [0.1, 0.3]; no
    # request waits in [0.4, 1.0]
    start, end = np.array([0.0, 0.3]), np.array([0.1, 0.4])
    dev = DeviceTrace(["x", "x"], start, end, start, end, [])
    log = np.array([[0.1, 0.2, 1.0]])
    run = make_run([0.0, 0.1], [0.0, 0.3], [0.1, 0.4], device=dev,
                   window=(0.0, 1.0), decide=log)
    assert reduce.breakdown(run)["idle_gaps"] == [
        ["no_request", pytest.approx(0.6)], ["decide", pytest.approx(0.2)]]


def test_logit_err_is_normalised_per_row():
    want = np.array([[0.0, 1.0, 2.0], [3.0, 0.0, 0.0]])
    got = want + np.array([[0.0, 0.0, 0.1], [0.3, 0.0, 0.0]])
    assert correctness.logit_err(got, want) == pytest.approx(
        max(0.1 / want[0].std(), 0.3 / want[1].std()))
    assert correctness.compare([], {}) == {"logit_err": float("inf")}


@pytest.mark.parametrize("completed,queued,unsub,dropped,errors", [
    ([0, 1, 2], [3], 1, 0, 0),           # all accounted for
    ([0, 1, 1], [3], 1, 0, 2),           # a duplicate, and 2 missing
    ([0, 1], [3], 1, 1, 0),              # 2 dropped
    ([0, 1, 2, 9], [3], 1, 0, 1),        # an id never sent
    ([0, 1], [3], 1, 0, 1),              # 2 lost
])
def test_accounting_errors(completed, queued, unsub, dropped, errors):
    assert correctness.accounting_errors(
        5, completed, queued, unsub, dropped) == errors


def test_capture_keeps_first_of_every_cell_and_a_seeded_sample():
    class Model:
        def execute(self, e, b):
            return (e, b)

    models = [Model(), Model()]
    cap = Capture(models, seed=2**40 + 1, k=2)
    for i in range(50):
        models[i % 2].execute(3, 1 + i % 3)
    picked = cap.picked()
    assert cap.count == 50
    cells = {(m, e, b) for m, e, b, _ in picked}
    assert cells >= {(0, 3, 1), (0, 3, 3), (0, 3, 2), (1, 3, 2), (1, 3, 1),
                     (1, 3, 3)}
    assert len(picked) <= 6 + 2
    runs = []
    for _ in range(2):
        again = Capture([], seed=2**40 + 1, k=2)
        for i in range(50):
            again.keep((i % 2, 3, 1 + i % 3, i))
        runs.append([x[3] for x in again.sample])
    assert runs[0] == runs[1] and len(runs[0]) == 2
