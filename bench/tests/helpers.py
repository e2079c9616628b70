"""A small benchmark tree for the CPU tests: the real model family and
metric readers, a test configuration of the trio's structure at CPU size
and a low-rate traffic mix."""

from __future__ import annotations

import contextlib
import json
import os
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]
REPO = BENCH.parent
DATA = pathlib.Path(__file__).resolve().parent / "data"

CELL = "tiny-trio.poisson"
BUSY = "tiny-trio.busy"          # queues build, so batches exceed one
SPEC = {
    "configs": [{"name": "tiny-trio", "file": "bench/configs/tiny-trio.json"}],
    "workloads": [{"name": CELL, "config": "tiny-trio",
                   "traffic": "tiny-poisson", "chips": 1},
                  {"name": BUSY, "config": "tiny-trio",
                   "traffic": "tiny-busy", "chips": 1}],
    "end_to_end": [{"name": n, "unit": "u"} for n in (
        "p95_ms", "attainment_pct", "mean_exit_depth", "setup_s")],
    "per_layer": [{"name": n, "unit": "u"} for n in (
        "queue_wait_ms", "decide_us", "quantum_over_profile",
        "step_mfu_pct", "device_idle_pct")],
}


def make_root(tmp: pathlib.Path) -> pathlib.Path:
    (tmp / "bench").mkdir(parents=True)
    for sub in ("metrics", "models"):
        shutil.copytree(BENCH / sub, tmp / "bench" / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for sub in ("configs", "traffic"):
        shutil.copytree(DATA / sub, tmp / "bench" / sub)
    (tmp / "BENCHMARK.json").write_text(json.dumps(SPEC))
    return tmp


@contextlib.contextmanager
def isolated_compile_cache(path: pathlib.Path):
    """Runs in this process compile into a cache of their own; JAX's cache
    settings are put back afterwards for the tests that share the process."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(path)
    try:
        yield
    finally:
        if env is None:
            del os.environ["JAX_COMPILATION_CACHE_DIR"]
        else:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = env
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
