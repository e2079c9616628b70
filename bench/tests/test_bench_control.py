"""The control of the correctness comparison, at a size a test run holds:
the plain reference put in the program's place and computed in the
precision each configuration names as its control must come out not
correct under that configuration's limits, while the program passes the
same comparison."""

import json
import time

import pytest

from bench import control, harness
from bench.models import early_exit_resnet_ref as ref
from bench.tests.helpers import (BENCH, CELL, DATA, isolated_compile_cache,
                                 make_root)
from repro.runtime.server import ServedModel

CONFIGS = ["resnet-trio-c100"]


@pytest.fixture(scope="module", autouse=True)
def compile_cache(tmp_path_factory):
    with isolated_compile_cache(tmp_path_factory.mktemp("jax-cache")):
        yield


@pytest.fixture(scope="module")
def tiny():
    return json.loads((DATA / "configs" / "tiny-trio.json").read_text())


def settings(name):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    return cfg["limits"], cfg["precision"]["control"]


@pytest.fixture(scope="module")
def readings(tiny):
    fam = harness.load_module(BENCH / "models" / "early_exit_resnet.py")
    cache, out = None, []
    for seed in (3, 2**35 + 1):
        program, ctrl, cache = control.readings(tiny, fam, seed, cache)
        out.append((program, ctrl))
    return out


@pytest.mark.parametrize("name", CONFIGS)
def test_program_passes_and_control_fails_each_configs_limits(
        readings, name):
    lim, low = settings(name)
    for program, ctrl in readings:
        assert program["logit_err"] <= lim["logit_err"]
        assert ctrl[low]["logit_err"] > lim["logit_err"]


def test_control_in_the_programs_place_is_not_correct(tiny, monkeypatch,
                                                       tmp_path):
    def execute(self, e, b):
        return ref.all_exits(self.values, self.data_fn(b), tiny, "fp8")[e]

    monkeypatch.setattr(ServedModel, "execute", execute)
    root = make_root(tmp_path)
    r = harness.run_cell(CELL, 17, 1.0, False, time.monotonic(), root=root,
                         require_tpu=False)
    assert r["correct"] is False
    assert r["checks"]["logit_err"]["value"] > r["checks"]["logit_err"][
        "limit"]
