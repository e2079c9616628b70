"""Readings that the correctness limits of a configuration are set from.

    python3 bench/control.py --config <config> --seeds 1,2,3,...

For each seed, in one process: the configuration's weights and inputs, the
program's served executables for every (model, exit, batch) (built once,
then reused across seeds: they take the weights as an argument), the plain
float32 reference, and each lower precision of the reference put in the
program's place (``CONTROLS``: the model cast to bfloat16 whole, and
float8 operands). Prints per seed the program's compared numbers against
the reference (the lower reading) and each control's, then the largest
program reading and each control's smallest. Each is also read against a
second reference kept in float32 but run at the served default precision
(``logit_err_default``), to see whether that one separates the program
from the bfloat16 cast. Needs a TPU.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


CONTROLS = ("bf16", "fp8")


def readings(cfg, fam, seed: int, executables=None, controls=CONTROLS):
    """``(program, {control: readings}, executables)`` for one seed: each
    reading a dict of the compared numbers, maxima over every served
    cell."""
    import numpy as np

    from bench import correctness

    built = fam.build(cfg, seed)
    if executables is not None:
        for model, cache in zip(built.models, executables):
            model.executables = cache
    program, control = [], {c: [] for c in controls}

    def against(served, refs, alt):
        out = correctness.compare(served, refs)
        out["logit_err_default"] = correctness.compare(
            served, alt)["logit_err"]
        return out

    for m, model in enumerate(built.models):
        w, x = built.weights[m], built.inputs[m]
        refs = {m: fam.reference(cfg, w, x)}
        alt = {m: fam.reference(cfg, w, x, "default")}
        cells = [(e, b) for e in range(model.num_exits)
                 for b in built.batch_sizes]
        program.append(against(
            [(m, e, b, np.asarray(model.execute(e, b))) for e, b in cells],
            refs, alt))
        for c in controls:
            low = fam.reference(cfg, w, x, c)
            control[c].append(against(
                [(m, e, b, low[b][e]) for e, b in cells], refs, alt))

    def worst(rows):
        return {k: max(r[k] for r in rows) for k in rows[0]}

    return (worst(program), {c: worst(r) for c, r in control.items()},
            [m.executables for m in built.models])


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--config", required=True)
    p.add_argument("--seeds", required=True)
    a = p.parse_args(argv)

    import jax
    from repro.runtime.compilation import enable_compile_cache

    from bench import harness

    if jax.devices()[0].platform != "tpu":
        print("bench/control.py: needs a TPU", file=sys.stderr)
        return 3
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cfg = harness.config(ROOT, harness.spec(ROOT), a.config)
    fam = harness.family(ROOT, cfg)
    lows, highs, cache = [], [], None
    for seed in (int(s) for s in a.seeds.split(",")):
        t = time.monotonic()
        prog, ctrl, cache = readings(cfg, fam, seed, cache)
        lows.append(prog)
        highs.append(ctrl)
        print(json.dumps({"seed": seed, "program": prog, "control": ctrl,
                          "seconds": time.monotonic() - t}), flush=True)
    print(json.dumps({
        "config": a.config, "seeds": len(lows),
        "lower": {k: max(r[k] for r in lows) for k in lows[0]},
        "controls": {c: {k: min(r[c][k] for r in highs)
                         for k in highs[0][c]} for c in highs[0]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
