"""Tests for the offline profile table (paper Sec. IV)."""

import numpy as np
import pytest

from repro.core import ProfileTable, paper_rate_vector


@pytest.fixture(scope="module")
def table():
    return ProfileTable.paper_rtx3080()


class TestPaperCalibration:
    def test_shape_is_paper_120_cells(self, table):
        # 3 models x 4 exits x 10 batch sizes (paper Sec. IV-B).
        assert table.latency.shape == (3, 4, 10)

    def test_batch_growth_2_to_3x(self, table):
        # Paper Fig. 2: batch 1 -> 10 raises latency ~2-3x, not 10x.
        ratio = table.latency[:, :, -1] / table.latency[:, :, 0]
        assert np.all(ratio >= 2.0) and np.all(ratio <= 3.0)

    def test_final_vs_layer1_6_to_8x_for_r152(self, table):
        r = table.latency[2, 3, :] / table.latency[2, 0, :]
        assert np.all(r >= 6.0) and np.all(r <= 8.0)

    def test_model_ordering(self, table):
        # R50 < R101 < R152 at every exit/batch; gap widest at final.
        assert np.all(table.latency[0] < table.latency[1])
        assert np.all(table.latency[1] < table.latency[2])
        gaps = table.latency[2] - table.latency[0]
        assert np.all(gaps[-1] >= gaps[0])

    def test_allfinal_saturation_near_paper_value(self, table):
        # Utilisation of the All-Final policy hits 1.0 near lambda_152 ~ 140
        # req/s (paper Fig. 4 knee: "degrades sharply beyond ~140 req/s").
        def util(lam):
            return sum(
                rate / 10.0 * table(m, 3, 10)
                for m, rate in enumerate(paper_rate_vector(lam))
            )
        assert util(140) < 1.0 < util(165)

    def test_accuracy_matches_table1(self, table):
        np.testing.assert_allclose(table.accuracy[0], [0.076, 0.121, 0.308, 0.744])
        np.testing.assert_allclose(table.accuracy[2, 3], 0.780)

    def test_monotone_in_batch(self, table):
        assert np.all(np.diff(table.latency, axis=2) >= 0)


class TestTableOps:
    def test_lookup_semantics(self, table):
        assert table(1, 2, 5) == table.latency[1, 2, 4]
        # batch beyond the profiled grid clamps to the largest entry
        assert table(1, 2, 99) == table.latency[1, 2, 9]

    def test_restrict_exits(self, table):
        sub = table.restrict_exits([0, 3])
        assert sub.exit_names == ("layer1", "final")
        assert sub.latency.shape == (3, 2, 10)
        np.testing.assert_array_equal(sub.latency[:, 1], table.latency[:, 3])

    def test_select_models(self, table):
        mix = table.select_models([0, 0, 0])
        assert mix.model_names == ("resnet50",) * 3
        np.testing.assert_array_equal(mix.latency[2], table.latency[0])

    def test_scaled_platform(self, table):
        slow = table.scaled(3.2, "gtx1650")
        np.testing.assert_allclose(slow.latency, table.latency * 3.2)
        assert slow.accuracy is table.accuracy  # accuracy platform-invariant

    def test_save_load_roundtrip(self, table, tmp_path):
        p = str(tmp_path / "profile.json")
        table.save(p)
        back = ProfileTable.load(p)
        np.testing.assert_allclose(back.latency, table.latency)
        np.testing.assert_allclose(back.accuracy, table.accuracy)
        assert back.model_names == table.model_names

    def test_measure_builder(self):
        import time
        calls = []

        def run_fn(m, e, b):
            calls.append((m, e, b))
            # millisecond-scale sleeps: sub-ms ones drown in OS scheduler
            # jitter and make the exit-ordering assertion below flaky.
            time.sleep(0.001 * (1 + m + e) * (1 + 0.1 * b))

        t = ProfileTable.measure(
            ["m0", "m1"], ["e0", "e1"], [1, 2], run_fn, repeats=9, warmup=1,
            percentile=50.0,
        )
        assert t.latency.shape == (2, 2, 2)
        assert np.all(t.latency > 0)
        # deeper exits cost more in this synthetic workload
        assert np.all(t.latency[:, 1, :] >= t.latency[:, 0, :] * 0.5)

    def test_rejects_nonmonotone_batch_latency(self):
        lat = np.ones((1, 1, 3))
        lat[0, 0] = [2.0, 1.0, 3.0]
        with pytest.raises(AssertionError):
            ProfileTable(("m",), ("e",), (1, 2, 3), lat, np.ones((1, 1)))

    def test_from_roofline_builder(self):
        t = ProfileTable.from_roofline(
            ["m"], ["e0", "e1"], [1, 2],
            terms_fn=lambda m, e, b: (1e-3 * (e + 1) * b, 0.5e-3, 0.1e-3),
            safety=1.0, dispatch_overhead_s=0.0,
        )
        # compute-bound everywhere here: L = compute term
        np.testing.assert_allclose(t.latency[0, :, 0], [1e-3, 2e-3])
        np.testing.assert_allclose(t.latency[0, :, 1], [2e-3, 4e-3])
