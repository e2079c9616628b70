"""Find a cell's knee on the chip: the highest total rate at which
attainment stays at or above 99% and the backlog does not grow.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <T> \
        --rates 500,1000,2000 [--refine 3]

One process: the set-up of a benchmark run of the cell's configuration once
(weights, executables, offline profile), then for each rate a fresh
scheduler and engine over the cell's own traffic file with only its total
rate replaced, from the same generator, drained up to the deadline.
``--refine k`` then bisects ``k`` times between the highest rate that held
and the lowest that did not. Prints one JSON line per rate and a table;
needs a TPU.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

ATTAINMENT_FLOOR = 99.0


def holds(row) -> bool:
    """Attainment at the floor, and no more queued at T than at T/2 plus
    one deadline's worth of arrivals."""
    return (row["attainment_pct"] >= ATTAINMENT_FLOOR
            and row["queued_end"] <= row["queued_mid"]
            + row["rate_rps"] * row["tau_s"])


def measure(dep, traffic: dict, rate: float, seconds: float, seed: int
            ) -> dict:
    import gc

    import numpy as np
    from repro.runtime.server import ServingEngine

    from bench import arrivals, harness, reduce

    tau = dep.cfg["slo_ms"] * 1e-3
    events = arrivals.generate({**traffic, "rate_rps": rate}, seconds, seed)
    engine = ServingEngine(dep.built.models, harness.scheduler(dep))
    reqs = harness.requests(events)
    gc.collect()    # as in a benchmark run: no set-up garbage in the window
    gc.freeze()
    engine.run(reqs, duration=seconds, drain=True,
               drain_cap=tau)
    run = harness.outcome(engine, events, seconds, tau)
    mid, end = reduce.queued_at(run, np.array([seconds / 2, seconds]))
    return {"rate_rps": rate, "tau_s": tau, "requests": len(events),
            "attainment_pct": reduce.attainment_pct(run),
            "p95_ms": reduce.p95_ms(run),
            "served_rps": reduce.served_rps(run),
            "mean_exit_depth": reduce.mean_exit_depth(run),
            "loop_gap_us": reduce.loop_gap_us(run),
            "queued_mid": int(mid), "queued_end": int(end)}


def sweep(workload: str, seed: int, seconds: float, rates, refine: int,
          require_tpu: bool = True, root=ROOT):
    import jax
    from repro.runtime.compilation import enable_compile_cache

    from bench import harness

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise harness.NoChip("the sweep needs a TPU")
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    bench = harness.spec(root)
    cell = harness.named(bench["workloads"], workload)
    cfg = harness.config(root, bench, cell["config"])
    traffic = harness.traffic(root, cell["traffic"])
    dep = harness.deploy(root, cfg, seed)
    print(f"setup {time.monotonic() - T_START:.3f}s "
          f"device={devices[0].device_kind}", flush=True)
    rows = []

    def one(rate):
        row = measure(dep, traffic, rate, seconds, seed)
        row["holds"] = holds(row)
        rows.append(row)
        print(json.dumps(row), flush=True)
        return row["holds"]

    for rate in rates:
        one(rate)
    for _ in range(refine):
        good = [r["rate_rps"] for r in rows if r["holds"]]
        bad = [r["rate_rps"] for r in rows if not r["holds"]]
        if not good or not bad or min(bad) < max(good):
            break
        one(round((max(good) + min(bad)) / 2))
    rows.sort(key=lambda r: r["rate_rps"])
    print("| rate req/s | attainment % | P95 ms | served req/s | exit depth "
          "| loop gap us | queued T/2 | queued T | holds |")
    print("|---|---|---|---|---|---|---|---|---|")
    for r in rows:
        print(f"| {r['rate_rps']} | {r['attainment_pct']:.3f} | "
              f"{r['p95_ms']:.3f} | {r['served_rps']:.1f} | "
              f"{r['mean_exit_depth']:.3f} | {r['loop_gap_us'] or 0:.1f} | "
              f"{r['queued_mid']} | "
              f"{r['queued_end']} | {'yes' if r['holds'] else 'no'} |")
    good = [r["rate_rps"] for r in rows if r["holds"]]
    knee = max(good) if good else None
    print(json.dumps({"workload": workload, "knee_rps": knee}), flush=True)
    return rows, knee


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--rates", required=True,
                   help="comma-separated total rates, req/s")
    p.add_argument("--refine", type=int, default=0)
    a = p.parse_args(argv)
    from bench import harness

    try:
        sweep(a.workload, a.seed, a.seconds,
              [float(r) for r in a.rates.split(",")], a.refine)
    except harness.NoChip as exc:
        print(f"bench/sweep.py: {exc}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
