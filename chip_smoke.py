"""Drive the live serving path once on one TPU chip and check what it serves.

The paper's deployment: early-exit ResNet-50/101/152 at published widths
(CIFAR-100 32x32 inputs, 100 classes, random weights from ``--seed``) share
one chip. The script runs, in one process:

  1. device check    - fails unless JAX's first device is a TPU;
  2. offline phase   - ``measure_profile`` over every exit and B in {1..4};
  3. online phase    - ``EdgeServingScheduler`` + ``ServingEngine.warmup``
                       + ``ServingEngine.run`` on a seeded 3:2:1 Poisson
                       trace, then drain;
  4. numerics        - deepest exit at B=2 on the chip against the CPU;
  5. scoring kernel  - the Pallas stability-score kernel, compiled for the
                       chip, against the float64 numpy scorer.

Any failed check raises, so the script exits non-zero. On success the last
line of stdout is ``{"ok": true, "device": {...}}``.

    python chip_smoke.py [--seed 0]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
# The numerics reference runs on the host's CPU backend: keep it available
# when the environment names the accelerator platform alone.
if os.environ.get("JAX_PLATFORMS", "cpu").split(",").count("cpu") == 0:
    os.environ["JAX_PLATFORMS"] += ",cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.edgeserving_resnets import FULL  # noqa: E402
from repro.core import (  # noqa: E402
    EdgeServingScheduler,
    LatticeEdgeServingScheduler,
    QueueSnapshot,
    SchedulerConfig,
    VectorizedEdgeServingScheduler,
    poisson_arrivals,
)
from repro.kernels.stability_score.ops import stability_scores  # noqa: E402
from repro.runtime.compilation import (  # noqa: E402
    CompileCounter,
    enable_compile_cache,
)
from repro.runtime.resnets import served_resnets  # noqa: E402
from repro.runtime.server import ServingEngine, measure_profile  # noqa: E402

# The paper profiles B in 1..10; B_max = 4 keeps a cold run's 48 compiles
# (3 models x 4 exits x 4 batch sizes) well inside the time limit.
BATCH_SIZES = (1, 2, 3, 4)
PAPER_MAX_BATCH = 10
SLO = 0.050                    # paper Sec. VI-A: tau = 50 ms
RATES = (30.0, 20.0, 10.0)     # 3:2:1, 60 req/s in all
HORIZON = 2.0                  # seconds of arrivals
NUMERICS_RTOL = 1e-3           # max |chip - cpu| / max |cpu logit|
SCORE_RTOL = 2e-4              # manifest bound of the float32 kernel


def require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def device_check():
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (first device is "
                 f"{dev.platform!r}); nothing was run")
    return dev


def offline_phase(configs, seed: int):
    t0 = time.perf_counter()
    with CompileCounter() as built:
        models = served_resnets(configs, seed)
        jax.block_until_ready([m.values for m in models])
    param_bytes = sum(x.nbytes for m in models
                      for x in jax.tree.leaves(m.values))
    with CompileCounter() as profiled:
        table = measure_profile(models, BATCH_SIZES)
    setup_s = time.perf_counter() - t0
    cells = sum(len(m.executables) for m in models)
    expect = sum(m.num_exits for m in models) * len(BATCH_SIZES)
    print(f"offline: models={','.join(m.name for m in models)} "
          f"param_bytes={param_bytes} setup_s={setup_s} "
          f"executables={cells} (expected {expect}) "
          f"compiles_init={built.count} compiles_profile={profiled.count}")
    print(f"offline: cut B_max={max(BATCH_SIZES)} (paper "
          f"B_max={PAPER_MAX_BATCH}) to keep a cold run short")
    for mi, name in enumerate(table.model_names):
        row = " ".join(f"{e}={table.latency[mi, ei, 0] * 1e3}ms"
                       for ei, e in enumerate(table.exit_names))
        print(f"offline: L({name}, e, B=1): {row}")
    require(cells == expect, f"{cells} executables, expected {expect}")
    return models, table


def online_phase(models, table, seed: int, dev):
    sched = EdgeServingScheduler(
        table, SchedulerConfig(slo=SLO, max_batch=max(BATCH_SIZES)))
    engine = ServingEngine(models, sched)
    with CompileCounter() as warm:
        engine.warmup()
    arrivals = poisson_arrivals(list(RATES), HORIZON, seed=seed)
    with CompileCounter() as window:
        _, span = engine.run(arrivals, duration=HORIZON, drain=True)
    m = engine.metrics(table, slo=SLO, span=span)
    arrived = len(arrivals)
    print(f"online: arrived={arrived} completed={m.num_completed} "
          f"dropped={m.dropped} residual={m.residual_queue} span_s={span}")
    print(f"online: p95_ms={m.p95_latency * 1e3} "
          f"violation_ratio={m.violation_ratio} "
          f"mean_exit_depth={m.mean_exit_depth} mean_batch={m.mean_batch}")
    print(f"online: compiles_warmup={warm.count} "
          f"compiles_in_window={window.count}")
    stats = dev.memory_stats() or {}
    print(f"online: peak_hbm_bytes={stats.get('peak_bytes_in_use')}")
    require(m.num_completed + m.dropped + m.residual_queue == arrived,
            "completed + dropped + residual != arrived")
    require(warm.count == 0, "warmup() compiled a cell the offline phase had")
    require(window.count == 0, "an executable was created while serving")


def numerics_phase(models):
    cpu = jax.devices("cpu")[0]
    with jax.default_matmul_precision("highest"):
        for mod in models:
            e = mod.num_exits - 1
            fn = jax.jit(lambda v, x, _f=mod.forward_fn, _e=e: _f(v, x, _e))
            x = mod.data_fn(2)
            got = np.asarray(fn(mod.values, x))
            ref = np.asarray(fn(jax.device_put(mod.values, cpu),
                                jax.device_put(x, cpu)))
            ratio = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
            print(f"numerics: {mod.name} exit={e} B=2 shape={got.shape} "
                  f"max_abs_err/max_abs_ref={ratio}")
            require(got.shape == ref.shape and np.isfinite(got).all(),
                    f"{mod.name}: non-finite or misshapen logits")
            require(ratio <= NUMERICS_RTOL,
                    f"{mod.name}: chip vs CPU error ratio {ratio}")


def scoring_phase(table, seed: int):
    rng = np.random.default_rng(seed)
    waits = [np.sort(rng.uniform(0.0, 0.06, n))[::-1] for n in (7, 5, 3)]
    snapshot = QueueSnapshot(0.0, waits)
    for cls in (VectorizedEdgeServingScheduler, LatticeEdgeServingScheduler):
        cfg = SchedulerConfig(slo=SLO, max_batch=max(BATCH_SIZES))
        ref = cls(table, cfg)
        dut = cls(table, dataclasses.replace(cfg, backend="pallas"))
        cq, cb, _, cl, _ = ref.enumerate_candidates(snapshot)
        s_ref = ref.score_candidates(snapshot, cl, cb, cq)
        s_dut = dut.score_candidates(snapshot, cl, cb, cq)
        d_ref, d_dut = ref.decide(snapshot), dut.decide(snapshot)
        pick_ref = (d_ref.model, d_ref.exit_idx, d_ref.batch_size)
        pick_dut = (d_dut.model, d_dut.exit_idx, d_dut.batch_size)
        err = float(np.max(np.abs(s_dut - s_ref) / np.abs(s_ref)))
        print(f"scoring: {dut.config.backend} vs numpy layout="
              f"{'lattice' if dut.config.lattice else 'greedy'} N={len(cq)} "
              f"interpret={dut.scoring.interpret} pick={pick_dut} "
              f"numpy_pick={pick_ref} max_rel_err={err}")
        np.testing.assert_allclose(s_dut, s_ref, rtol=SCORE_RTOL)
        require(pick_dut == pick_ref,
                f"pallas picked {pick_dut}, numpy picked {pick_ref}")
    w, mask = snapshot.padded()
    hlo = stability_scores.lower(
        jnp.asarray(w, jnp.float32), jnp.asarray(mask, jnp.float32),
        jnp.asarray(cl, jnp.float32), jnp.asarray(cb, jnp.int32),
        jnp.asarray(cq, jnp.int32), tau=jnp.float32(SLO),
        clip=jnp.float32(10.0), interpret=dut.scoring.interpret).as_text()
    compiled = "tpu_custom_call" in hlo
    print(f"scoring: kernel lowered as tpu_custom_call={compiled}")
    require(dut.scoring.interpret is False and compiled,
            "the scoring kernel did not run compiled on the chip")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    dev = device_check()
    print(f"compile cache: {enable_compile_cache()}")
    models, table = offline_phase(FULL, args.seed)
    online_phase(models, table, args.seed, dev)
    numerics_phase(models)
    scoring_phase(table, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
