"""Runs one cell of ``BENCHMARK.json`` once, on the served path.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``: sizes, deadline, batch limit, served
  precision and correctness limits; its ``model`` names the family module
  ``bench/models/<model>.py`` (build, FLOPs, reference);
* ``bench/traffic/<traffic>.json``: parameters for ``bench/arrivals.py``;
* ``bench/metrics/<metric>.py``: a ``read(run)`` that returns the metric
  or ``None``.

A run, in order: device check, compile cache, weights and inputs from the
seed, every executable built or loaded, the offline profile
(``measure_profile``), the scheduler (``EdgeServingScheduler``, numpy,
paper-exact Algorithm 1) and ``ServingEngine.warmup``, the arrival trace,
then ``ServingEngine.run`` over ``[0, T)`` with a drain capped at the
deadline. Set-up ends when the engine starts. After the window: metrics,
then the program's executables are dropped and the reference checks what
the window's quanta returned.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import pathlib
import random
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List

import numpy as np

from bench import arrivals, correctness, reduce

ROOT = pathlib.Path(__file__).resolve().parents[1]
# A traced run starts the profiler before the engine and stops it
# TRACE_TO seconds into the window, between two quanta; per-layer metrics
# read [TRACE_FROM, TRACE_TO]. The device writes about a million events a
# second, and stopping the profiler costs ~30 us per event, so the traced
# part is kept short; what follows the stop is not read.
TRACE_FROM, TRACE_TO = 0.25, 1.25


class NoChip(RuntimeError):
    """The cell's chips are not there."""


# -- discovery ---------------------------------------------------------------

def load_json(path: pathlib.Path):
    with open(path) as f:
        return json.load(f)


def spec(root: pathlib.Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def named(items: List[dict], name: str) -> dict:
    for item in items:
        if item["name"] == name:
            return item
    raise KeyError(f"no entry named {name!r}")


def config(root: pathlib.Path, bench: dict, name: str) -> dict:
    return load_json(root / named(bench["configs"], name)["file"])


def traffic(root: pathlib.Path, name: str) -> dict:
    return load_json(root / "bench" / "traffic" / f"{name}.json")


def load_module(path: pathlib.Path):
    name = "bench_file_" + "_".join(path.with_suffix("").parts[-2:]).replace(
        ".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec_ = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec_)
    sys.modules[name] = module
    spec_.loader.exec_module(module)
    return module


def family(root: pathlib.Path, cfg: dict):
    return load_module(root / "bench" / "models" / f"{cfg['model']}.py")


def reader(root: pathlib.Path, metric: str):
    return load_module(root / "bench" / "metrics" / f"{metric}.py").read


def cell_metrics(bench: dict, workload: str, trace: bool) -> List[dict]:
    """The metrics a run of ``workload`` reports: the end-to-end ones
    untraced, the per-layer ones traced."""
    kind = "per_layer" if trace else "end_to_end"
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


# -- set-up -------------------------------------------------------------------

@dataclasses.dataclass
class Deployment:
    cfg: dict
    built: Any
    table: Any                 # repro ProfileTable
    phases: Dict[str, float]
    executables: int


def say(*parts, file=None):
    print(*parts, file=file or sys.stdout, flush=True)


def deploy(root: pathlib.Path, cfg: dict, seed: int) -> Deployment:
    """Weights, executables and the offline profile of one configuration."""
    import jax
    from repro.runtime.compilation import CompileCounter
    from repro.runtime.server import measure_profile

    fam = family(root, cfg)
    phases = {}
    t = time.monotonic()
    built = fam.build(cfg, seed)
    jax.block_until_ready((built.weights, built.inputs))
    phases["weights"] = time.monotonic() - t
    t = time.monotonic()
    with CompileCounter() as built_count:
        for model in built.models:
            for e in range(model.num_exits):
                for b in built.batch_sizes:
                    model.execute(e, b)
    phases["executables"] = time.monotonic() - t
    t = time.monotonic()
    table = measure_profile(built.models, built.batch_sizes, built.exit_names)
    phases["offline_profile"] = time.monotonic() - t
    return Deployment(cfg, built, table, phases, built_count.count)


def scheduler(dep: Deployment):
    from repro.core.scheduler import EdgeServingScheduler, SchedulerConfig

    return EdgeServingScheduler(dep.table, SchedulerConfig(
        slo=dep.cfg["slo_ms"] * 1e-3, max_batch=dep.cfg["max_batch"]))


def requests(events):
    from repro.core.request import Request

    return [Request(req_id=i, model=m, arrival=t, data_id=d)
            for i, (t, m, d) in enumerate(events)]


class Capture:
    """Keeps some quanta's outputs for the comparison: the first quantum of
    every (model, exit, batch) served, and a reservoir sample of ``k`` more
    drawn from the seed. Each model's ``execute`` (an instance attribute,
    so the engine calls it unchanged) passes its output through here."""

    def __init__(self, models, seed: int, k: int = 32):
        self.first: dict = {}
        self.sample: list = []
        self.count = 0
        self.k = k
        self._rng = random.Random(int(seed))
        for m, model in enumerate(models):
            orig = model.execute

            def execute(e, b, _m=m, _orig=orig):
                out = _orig(e, b)
                self.keep((_m, e, b, out))
                return out

            model.execute = execute

    def keep(self, item) -> None:
        self.first.setdefault(item[:3], item)
        if self.count < self.k:
            self.sample.append(item)
        else:
            j = self._rng.randrange(self.count + 1)
            if j < self.k:
                self.sample[j] = item
        self.count += 1

    def picked(self) -> list:
        """The kept quanta, each once."""
        seen, out = set(), []
        for item in [*self.first.values(), *self.sample]:
            if id(item) not in seen:
                seen.add(id(item))
                out.append(item)
        return out


def outcome(engine, events, seconds: float, tau: float) -> reduce.Run:
    n = len(events)
    dispatch = np.full(n, np.nan)
    finish = np.full(n, np.nan)
    exit_idx = np.full(n, -1)
    batch = np.zeros(n, dtype=int)
    for c in engine.completions:
        dispatch[c.req_id] = c.dispatch
        finish[c.req_id] = c.finish
        exit_idx[c.req_id] = c.exit_idx
        batch[c.req_id] = c.batch_size
    return reduce.Run(
        seconds=seconds, tau=tau,
        arrival=np.array([e[0] for e in events], dtype=np.float64),
        model=np.array([e[1] for e in events], dtype=int),
        dispatch=dispatch, finish=finish, exit_idx=exit_idx, batch=batch)


def account(engine, n: int) -> int:
    queued = [r.req_id for q in engine.queues for r in q.pending()]
    unsubmitted = engine.counters["drain_residual"] - len(queued)
    return correctness.accounting_errors(
        n, (c.req_id for c in engine.completions), queued, unsubmitted,
        engine.dropped)


# -- one run -----------------------------------------------------------------

def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, root: pathlib.Path = ROOT,
             require_tpu: bool = True, err=None) -> dict:
    """One run of ``workload``; returns the result line's object. Prints
    the set-up split on earlier lines of stdout and the compared numbers,
    each with its limit, as the last lines of ``err``."""
    import jax
    from repro.runtime.compilation import CompileCounter, enable_compile_cache
    from repro.runtime.server import ServingEngine

    err = err or sys.stderr
    bench = spec(root)
    cell = named(bench["workloads"], workload)
    cfg = config(root, bench, cell["config"])
    mix = traffic(root, cell["traffic"])
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < cell["chips"]):
        raise NoChip(f"cell {workload} needs {cell['chips']} TPU chip(s); "
                     f"JAX found {len(devices)} {dev.platform} device(s)")
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    phases = {"imports_and_device": time.monotonic() - t_start}

    dep = deploy(root, cfg, seed)
    phases.update(dep.phases)
    tau = cfg["slo_ms"] * 1e-3
    engine = ServingEngine(dep.built.models, scheduler(dep))
    t = time.monotonic()
    with CompileCounter() as warm:
        engine.warmup()
    phases["warmup"] = time.monotonic() - t
    t = time.monotonic()
    events = arrivals.generate(mix, seconds, seed)
    reqs = requests(events)
    phases["arrivals"] = time.monotonic() - t

    captured = Capture(dep.built.models, seed)
    probe = None
    if trace:
        from bench.trace import DecideProbe

        logdir = pathlib.Path(tempfile.mkdtemp(prefix="bench-trace-"))
        probe = DecideProbe(engine.scheduler, logdir, TRACE_TO)
    # Set-up leaves millions of objects (traced programs, the trace's
    # requests); collect them now and keep the survivors out of later
    # collections, so that no collection of set-up garbage lands in the
    # window.
    gc.collect()
    gc.freeze()
    pauses = GcPauses()
    with CompileCounter() as window, pauses:
        if probe:
            probe.start()
        t0 = time.monotonic()
        if probe:
            probe.t0 = t0
        engine.run(reqs, duration=seconds, drain=True, drain_cap=tau)
    t_end = time.monotonic() - t0
    setup_s = t0 - t_start
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}

    say(f"setup: {' '.join(f'{k}={v:.3f}s' for k, v in phases.items())}")
    say(f"setup: executables={dep.executables} built or loaded; "
        f"warmup compiles={warm.count}; window compiles={window.count}")
    say(f"setup_s={setup_s:.6f} window_end={t_end:.3f}s "
        f"requests={len(events)} quanta={captured.count} "
        f"gc_collections={pauses.count} gc_longest_ms={pauses.longest * 1e3:.3f}")
    _print_accuracy(cfg, dep)

    run = outcome(engine, events, seconds, tau)
    run.setup_s = setup_s
    run.latency_table = dep.table.latency
    run.batch_sizes = tuple(dep.table.batch_sizes)
    fam = family(root, cfg)
    run.flops = lambda m, e, b: fam.flops(cfg, m, e, b)
    run.device_kind = dev.device_kind
    run.peak_key = cfg["precision"]["peak"]
    say(late_line(run))
    if probe is not None:
        from bench import trace as trace_mod

        probe.stop()
        run.decide = probe.log()
        if probe.stopped is not None:
            run.window = (TRACE_FROM, probe.stopped)
            run.device = trace_mod.read(logdir, run.decide)
            if run.device is not None:
                for line in run.device.planes:
                    say(f"trace: {line}")
        shutil.rmtree(logdir, ignore_errors=True)

    metrics = {}
    for m in cell_metrics(bench, workload, trace):
        value = reader(root, m["name"])(run)
        if value is None:
            say(f"metric {m['name']}: nothing to read", file=err)
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if trace:
        device["busy_s"] = reduce.busy_s(run)
        device["window_s"] = reduce.window_s(run)

    checks = {"accounting_errors": float(account(engine, len(events)))}
    checks.update(check_outputs(fam, cfg, dep, captured))
    limits = cfg["limits"]
    ok = correctness.verdict(checks, limits)
    done = int(run.completed.sum())
    result = {"correct": ok, "attempted": len(events),
              "failed": len(events) - done, "metrics": metrics,
              "device": device}
    if trace:
        bd = reduce.breakdown(run)
        if bd is not None:
            result["breakdown"] = bd
    result["checks"] = {k: {"value": v, "limit": limits.get(k)}
                        for k, v in checks.items()}
    for k, v in checks.items():
        say(f"check {k}={v!r} limit={limits.get(k)!r}", file=err)
    return result


def check_outputs(fam, cfg, dep: Deployment, captured: Capture):
    """Compare the kept quanta with the reference, after the program's
    executables are dropped."""
    import jax

    served = [(m, e, b, np.asarray(out))
              for m, e, b, out in captured.picked()]
    captured.first.clear()
    captured.sample.clear()
    for model in dep.built.models:
        model.executables.clear()
    jax.clear_caches()
    refs = {m: fam.reference(cfg, dep.built.weights[m], dep.built.inputs[m])
            for m in sorted({s[0] for s in served})}
    say(f"compared {len(served)} quanta "
        f"({sum(s[2] for s in served)} requests) with the reference")
    return correctness.compare(served, refs)


class GcPauses:
    """Counts the interpreter's garbage collections while open and keeps
    the longest pause."""

    def __init__(self):
        self.count, self.longest, self._t = 0, 0.0, 0.0

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.monotonic()
        else:
            self.count += 1
            self.longest = max(self.longest, time.monotonic() - self._t)

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)


def late_line(run: reduce.Run) -> str:
    """Where the misses are: how many requests missed, the span of their
    due times and the worst latency."""
    lat = run.finish - run.arrival
    late = ~(np.isfinite(lat) & (lat <= run.tau))
    if not late.any():
        return "late: 0"
    worst = np.nanmax(lat) if np.isfinite(lat).any() else float("nan")
    return (f"late: {int(late.sum())} due in [{run.arrival[late].min():.3f}, "
            f"{run.arrival[late].max():.3f}] s; worst {worst * 1e3:.3f} ms")


def _print_accuracy(cfg: dict, dep: Deployment) -> None:
    """The paper's Table I accuracy, for the trio at CIFAR-100 only."""
    from repro.core.profile import ProfileTable

    paper = ProfileTable.paper_rtx3080()
    names = [m["name"] for m in cfg["models"]]
    if cfg["num_classes"] == 100 and names == list(paper.model_names):
        for name, row in zip(names, paper.accuracy):
            say(f"paper Table I top-1 accuracy {name}: "
                + " ".join(f"{e}={a:.3f}" for e, a in
                           zip(paper.exit_names, row)))
