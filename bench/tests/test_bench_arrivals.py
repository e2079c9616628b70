"""The benchmark's copy of the arrival arithmetic against the program's."""

import numpy as np
import pytest

from bench import arrivals
from repro.core.workloads import MMPPProcess, PoissonProcess

RATES = [1500.0, 1000.0, 500.0]


def program_trace(process, horizon, seed):
    return [(r.arrival, r.model, r.data_id)
            for r in process.generate(horizon, seed=seed)]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_poisson_copy_is_bitwise_the_programs(seed):
    ours = arrivals.generate({"process": "poisson", "count": "poisson",
                              "rate_rps": 3000.0, "mix": [3, 2, 1]},
                             2.0, seed)
    assert ours == program_trace(PoissonProcess(RATES), 2.0, seed)


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_mmpp_copy_is_bitwise_the_programs(seed):
    ours = arrivals.generate({"process": "mmpp", "rate_rps": 3000.0,
                              "mix": [3, 2, 1], "burst": 3.0, "duty": 0.25,
                              "cycle": 2.0}, 6.0, seed)
    assert ours == program_trace(MMPPProcess(RATES), 6.0, seed)


def test_fixed_count_gives_every_seed_the_same_work():
    traffic = {"process": "poisson", "count": "fixed", "rate_rps": 3000.0,
               "mix": [3, 2, 1]}
    counts = []
    for seed in (1, 2, 2**33):
        ev = arrivals.generate(traffic, 2.0, seed)
        t = np.array([e[0] for e in ev])
        assert np.all(np.diff(t) >= 0) and t.min() >= 0 and t.max() < 2.0
        counts.append(np.bincount([e[1] for e in ev]).tolist())
    assert counts == [[3000, 2000, 1000]] * 3
    assert (arrivals.generate(traffic, 2.0, 5)
            == arrivals.generate(traffic, 2.0, 5))
    assert (arrivals.generate(traffic, 2.0, 5)
            != arrivals.generate(traffic, 2.0, 6))
