"""End-to-end live serving driver (the paper's kind of deployment, real
execution): three early-exit LMs of increasing cost share one accelerator
under time-division; the offline phase measures the real profile table;
the online phase serves a Poisson trace with the EdgeServing scheduler and
reports SLO compliance. Everything here runs the actual jitted models.

  PYTHONPATH=src python examples/serve_multi_model.py [--duration 3.0]
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import EdgeServingScheduler, SchedulerConfig, poisson_arrivals
from repro.models import build_model, split_params
from repro.models.transformer import LMConfig
from repro.runtime.compilation import enable_compile_cache
from repro.runtime.server import ServedModel, ServingEngine, measure_profile


def make_deployment():
    """Three early-exit LMs: cost ordering mimics R50 < R101 < R152."""
    models = []
    for i, (layers, d) in enumerate([(2, 64), (2, 128), (4, 128)]):
        cfg = LMConfig(
            arch_id=f"lm{i}", family="dense", num_layers=layers,
            d_model=d, num_heads=4, num_kv_heads=2, d_ff=4 * d,
            vocab_size=512, exits=tuple(range(1, layers + 1)),
        )
        model = build_model(cfg)
        values, _ = split_params(model.init(jax.random.key(i)))

        def forward(v, x, e, _m=model):
            return _m.forward_exit(v, {"tokens": x}, e)

        def data(b, _v=cfg.vocab_size):
            return jnp.zeros((b, 16), jnp.int32)

        models.append(ServedModel(
            name=f"lm{i}-{layers}L-d{d}", values=values, forward_fn=forward,
            data_fn=data, num_exits=cfg.num_exits))
    # pad exit counts: profile table needs uniform E -> use min
    e_min = min(m.num_exits for m in models)
    for m in models:
        m.num_exits = e_min
    return models


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--duration", type=float, default=3.0)
    ap.add_argument("--rate", type=float, default=150.0,
                    help="total request rate (req/s), 3:2:1 split")
    args = ap.parse_args()

    enable_compile_cache()
    models = make_deployment()
    print("== offline profiling phase (real wall-clock, this machine) ==")
    table = measure_profile(models, batch_sizes=[1, 2, 4, 8], repeats=5,
                            warmup=2)
    for mi, name in enumerate(table.model_names):
        lat = ", ".join(
            f"{e}={table.latency[mi, ei, 0]*1e3:.2f}ms"
            for ei, e in enumerate(table.exit_names))
        print(f"  {name}: B=1 {lat}")

    # SLO: 5x the slowest profiled quantum (CPU latencies are ~ms-scale)
    slo = float(table.latency.max() * 5)
    print(f"SLO tau = {slo*1e3:.1f} ms")

    cfg = SchedulerConfig(slo=slo, max_batch=8)
    engine = ServingEngine(models, EdgeServingScheduler(table, cfg))
    print("== warmup: every (m, e, B) was compiled while profiling ==")
    engine.warmup([1, 2, 4, 8])

    unit = args.rate / 6.0
    arrivals = poisson_arrivals([3 * unit, 2 * unit, unit], args.duration,
                                seed=42)
    print(f"== online serving phase: {len(arrivals)} requests over "
          f"{args.duration:.1f}s ==")
    completions, span = engine.run(arrivals, args.duration, drain=True)
    m = engine.metrics(table, slo=slo, span=span)
    print(f"completed={m.num_completed} dropped={m.dropped} "
          f"P95={m.p95_latency*1e3:.2f}ms violations={m.violation_ratio*100:.2f}% "
          f"mean_exit_depth={m.mean_exit_depth:.2f} util={m.utilization:.2f}")
    exits = np.array([c.exit_idx for c in completions])
    for e in range(int(exits.max()) + 1):
        print(f"  exit {e}: {np.mean(exits == e)*100:.1f}% of requests")


if __name__ == "__main__":
    main()
