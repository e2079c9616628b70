"""The harness on the CPU at a test size: a whole run through its own
functions, discovery of new files by name, the faults the comparison has
to catch, and the refusal to run without a TPU."""

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import harness
from bench.tests.helpers import (BUSY, CELL, REPO, isolated_compile_cache,
                                 make_root)
from repro.runtime.server import ServedModel, ServingEngine

ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
       "PYTHONPATH": str(REPO / "src")}
FIRST_CELL = harness.spec(REPO)["workloads"][0]["name"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("benchroot"))


@pytest.fixture(scope="module", autouse=True)
def compile_cache(tmp_path_factory):
    with isolated_compile_cache(tmp_path_factory.mktemp("jax-cache")):
        yield


def run(root, trace=False, seed=2**33 + 5, cell=CELL):
    return harness.run_cell(cell, seed, 1.0, trace, time.monotonic(),
                            root=root, require_tpu=False)


def test_rehearsal_untraced(root):
    r = run(root)
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"] and list(r)[-1] == "checks"
    assert r["correct"] is True
    assert r["attempted"] == 60 and r["failed"] == 0
    assert set(r["metrics"]) == {"p95_ms", "attainment_pct",
                                 "mean_exit_depth", "setup_s"}
    assert r["device"]["platform"] == "cpu"
    assert r["checks"]["accounting_errors"]["value"] == 0


def test_rehearsal_traced_reads_no_device_metric_on_cpu(root):
    r = run(root, trace=True)
    assert r["correct"] is True
    assert {"queue_wait_ms", "decide_us", "quantum_over_profile"} <= set(
        r["metrics"])
    # a CPU run has no device plane: no device metric, no breakdown
    assert "device_idle_pct" not in r["metrics"]
    assert "step_mfu_pct" not in r["metrics"]
    assert "breakdown" not in r
    assert r["device"]["busy_s"] is None


def test_new_files_are_found_by_name(root, tmp_path):
    new = tmp_path / "r"
    shutil.copytree(root, new)
    cfg = json.loads((new / "bench/configs/tiny-trio.json").read_text())
    cfg["models"] = cfg["models"][:1]
    (new / "bench/configs/tiny-solo.json").write_text(json.dumps(cfg))
    (new / "bench/traffic/tiny-burst.json").write_text(json.dumps(
        {"process": "mmpp", "rate_rps": 40, "mix": [1], "burst": 3.0,
         "duty": 0.25, "cycle": 0.5}))
    (new / "bench/metrics/requests_done.py").write_text(
        "def read(run):\n    return float(run.completed.sum())\n")
    spec = json.loads((new / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-solo",
                            "file": "bench/configs/tiny-solo.json"})
    spec["workloads"].append({"name": "tiny-solo.burst", "config":
                              "tiny-solo", "traffic": "tiny-burst",
                              "chips": 1})
    spec["end_to_end"].append({"name": "requests_done", "unit": "req",
                               "workloads": ["tiny-solo.burst"]})
    (new / "BENCHMARK.json").write_text(json.dumps(spec))
    r = harness.run_cell("tiny-solo.burst", 3, 1.0, False, time.monotonic(),
                         root=new, require_tpu=False)
    assert r["correct"] is True
    done = r["attempted"] - r["failed"]
    assert r["metrics"]["requests_done"]["value"] == done > 0


def test_sweep_scales_the_cells_own_traffic_file(root, monkeypatch):
    from bench import arrivals, sweep

    seen = []
    generate = arrivals.generate

    def record(traffic, horizon, seed):
        seen.append(dict(traffic))
        return generate(traffic, horizon, seed)

    monkeypatch.setattr(arrivals, "generate", record)
    rows, _ = sweep.sweep(CELL, 3, 1.0, [20.0, 40.0], 0, require_tpu=False,
                          root=root)
    want = json.loads((root / "bench/traffic/tiny-poisson.json").read_text())
    assert seen == [{**want, "rate_rps": 20.0}, {**want, "rate_rps": 40.0}]
    assert [r["requests"] for r in rows] == [20, 40]


def altered_answer(monkeypatch):
    orig = ServedModel.execute

    def execute(self, e, b):
        out = np.asarray(orig(self, e, b)).copy()
        out[-1, 0] += 1.0
        return out

    monkeypatch.setattr(ServedModel, "execute", execute)


def half_batch(monkeypatch):
    orig = ServedModel.execute

    def execute(self, e, b):
        out = np.asarray(orig(self, e, b)).copy()
        if b > 1:
            keep = (b + 1) // 2
            out[keep:] = out[:keep].mean(axis=0)
        return out

    monkeypatch.setattr(ServedModel, "execute", execute)


def lost_request(monkeypatch):
    orig = ServingEngine.run

    def run(self, *args, **kwargs):
        out = orig(self, *args, **kwargs)
        self.completions.pop(len(self.completions) // 2)
        return out

    monkeypatch.setattr(ServingEngine, "run", run)


@pytest.mark.parametrize("fault,check", [
    (altered_answer, "logit_err"),
    (half_batch, "logit_err"),
    (lost_request, "accounting_errors"),
])
def test_faults_make_correct_false(root, monkeypatch, fault, check):
    fault(monkeypatch)
    r = run(root, seed=41, cell=BUSY)
    assert r["correct"] is False
    assert r["checks"][check]["value"] > r["checks"][check]["limit"]


def test_run_exits_nonzero_without_a_tpu():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         FIRST_CELL, "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=ENV, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 3
    assert p.stdout == ""
    assert "TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in ENV.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         FIRST_CELL, "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
