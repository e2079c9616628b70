"""The precision manifest: every numerics contract in one declarative place.

Four declarations drive the analyzers (docs/static-analysis.md documents
the format):

  * **Path contracts** — ``FLOAT64_PATHS`` names the repo-relative prefixes
    whose scheduling arithmetic must stay float64 (DET005 scope);
    ``ENGINE_MODULES`` names the simulated-time engines where wall-clock
    reads are banned (DET002 scope); ``TIMING_ALLOWLIST`` /
    ``FLOAT32_ALLOWANCES`` carve out the documented exceptions, each with a
    justification that the docs render verbatim.
  * **Traced artifacts** — ``PRECISION_ARTIFACTS`` names the compiled
    functions the jaxpr auditor traces, with their dtype contract. A
    ``float64`` contract means *no* float32/float16/bfloat16 value may
    appear anywhere in the jaxpr; a ``float32`` contract is a declared
    downcast tier and carries the ``rtol`` bound that its tolerance test
    (``tests/test_analysis.py``) enforces against the float64 reference.
  * **Recompile guards** — ``RECOMPILE_GUARDS`` generalize the PR 4
    ``_cache_size`` test: sweeping traced operands (tau / clip / deadline
    matrices) through a compiled artifact must not grow its compile cache.
  * **Kernel envelopes** — ``KERNEL_SPECS`` gives each ``kernels/*``
    Pallas kernel a representative deployment shape and a VMEM budget; the
    Pallas auditor captures the real ``pallas_call`` layout at that shape
    and checks divisibility, index-map bounds, footprint, and explicit
    memory-space annotations.

Builders import jax lazily so the AST layer stays import-light.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

__all__ = [
    "Allowance", "ArtifactSpec", "RecompileGuard", "KernelSpec",
    "ENGINE_MODULES", "TIMING_ALLOWLIST", "FLOAT64_PATHS",
    "FLOAT32_ALLOWANCES", "PRECISION_ARTIFACTS", "RECOMPILE_GUARDS",
    "KERNEL_SPECS", "VMEM_BUDGET_BYTES",
]


@dataclasses.dataclass(frozen=True)
class Allowance:
    """A documented exception to a path contract, scoped to a qualname."""

    path: str           # repo-relative file
    scope: str          # enclosing qualname (prefix match)
    justification: str  # rendered in docs; required


@dataclasses.dataclass(frozen=True)
class ArtifactSpec:
    """A compiled artifact the jaxpr auditor traces.

    ``build()`` returns ``(fn, args, kwargs)``; the auditor runs
    ``jax.make_jaxpr(fn)(*args, **kwargs)`` (under ``jax.enable_x64(True)`` when
    ``x64``) and checks the dtype contract + primitive denylist.
    ``rtol`` is the declared kernel-vs-float64-reference error bound for
    ``float32``-contract artifacts (enforced by the tolerance test).
    """

    name: str
    dtype_contract: str                     # "float64" | "float32"
    build: Callable[[], Tuple[Any, tuple, dict]]
    x64: bool = True
    rtol: Optional[float] = None
    notes: str = ""


@dataclasses.dataclass(frozen=True)
class RecompileGuard:
    """A compiled artifact that must not recompile across a value sweep.

    ``build()`` returns ``(fn, calls)`` where ``fn`` exposes jax's
    ``_cache_size`` and ``calls`` is a list of ``(args, kwargs)``. The
    first call primes the cache; the remainder must not grow it.
    """

    name: str
    build: Callable[[], Tuple[Any, list]]
    x64: bool = False       # run the sweep under jax.enable_x64(True)
    notes: str = ""


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One ``kernels/*`` kernel with its audited deployment envelope."""

    name: str
    build: Callable[[], Tuple[Any, tuple, dict]]   # fn(*args, **kwargs)
    vmem_budget_bytes: int = 16 * 1024 * 1024      # ~one TPU core of VMEM
    notes: str = ""


VMEM_BUDGET_BYTES = 16 * 1024 * 1024


# ---------------------------------------------------------------------------
# Path contracts (Layer 1 scope)
# ---------------------------------------------------------------------------

# Engines evolve *simulated* time; the only wall-clock they may see is
# injected (ServingEngine's `clock=` parameter lives in runtime/, not here).
ENGINE_MODULES: Tuple[str, ...] = (
    "src/repro/core/simulator.py",
    "src/repro/core/simfast.py",
    "src/repro/core/cluster.py",
    "src/repro/core/clusterfast.py",
    "src/repro/core/seedband.py",
    "src/repro/core/telemetry.py",
)

# (path, qualname, justification) triples for sanctioned wall-clock reads
# inside engine modules. Empty today: the engines are clean.
TIMING_ALLOWLIST: Tuple[Allowance, ...] = ()

# All scheduling arithmetic under core/ is float64-contract: the scan
# engine's bitwise equality, parallel==serial sweeps, and the golden fig4/
# fig12 metrics all assume IEEE-identical float64 ops. The stability-score
# ops wrapper is also in scope: it is the one sanctioned f64 -> f32
# boundary (scheduler world -> kernel world), and keeping it under DET005
# forces every downcast there to carry an inline suppression pointing at
# its tolerance bound.
FLOAT64_PATHS: Tuple[str, ...] = (
    "src/repro/core/",
    "src/repro/kernels/stability_score/ops.py",
)

FLOAT32_ALLOWANCES: Tuple[Allowance, ...] = (
    Allowance(
        "src/repro/core/scoring.py", "JnpScoringBackend.score",
        "the jnp backend is the declared float32 accelerated tier: inputs "
        "are downcast at this boundary only, decision equivalence vs the "
        "float64 reference is property-tested (tests/test_scoring.py) and "
        "the score error bound is pinned by the stability_score tolerance "
        "test (tests/test_analysis.py)."),
    Allowance(
        "src/repro/core/scoring.py", "PallasScoringBackend.score",
        "the Pallas backend feeds the float32 VMEM kernel "
        "(kernels/stability_score); same declared boundary and tolerance "
        "bound as the jnp backend."),
)


# ---------------------------------------------------------------------------
# Layer 2: traced artifacts
# ---------------------------------------------------------------------------


def _sds(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype)


def _build_lattice_scores():
    """Eq. 4-7 float64 reference scoring (shared by backends + scan)."""
    import numpy as np
    from repro.core.urgency import lattice_stability_scores

    m, q, n = 3, 8, 6
    return lattice_stability_scores, (
        _sds((m, q), np.float64), _sds((m, q), np.float64),
        _sds((n,), np.float64), _sds((n,), np.int64),
        _sds((n,), np.int64), 0.05, 10.0,
    ), {}


def _scan_chunk_key():
    from repro.core.simfast import _StaticKey

    # Tiny but fully exercising key: 2 models, 2 exits, greedy single-rung
    # ladder for caps 0..2, margin aux emission on.
    return _StaticKey(
        num_models=2, num_exits=2, max_queue=4, pad_len=8, chunk_steps=4,
        max_batch=2, ladder=((0,), (1,), (2,)), allowed=(True, True),
        fallback_exit=0, clip=10.0, factored=True, emit_aux=True,
    )


def _build_scan_step(factored: bool):
    import numpy as np
    from repro.core.simfast import _build_chunk_fn

    key = dataclasses.replace(_scan_chunk_key(), factored=factored)
    fn = _build_chunk_fn(key)
    lanes, m, e, p = 2, key.num_models, key.num_exits, key.pad_len
    b1, r = key.max_batch + 1, len(key.ladder[0])
    carry = (
        _sds((lanes,), np.float64), _sds((lanes, m), np.int32),
        _sds((lanes,), np.float64), _sds((lanes,), np.bool_),
        _sds((lanes,), np.bool_),
    )
    args = (
        carry,
        _sds((lanes, m, p, 2), np.float64),          # arrivals + exp factors
        _sds((m, b1, e, r), np.float64),             # belief latency by cap
        _sds((m, e, b1), np.float64),                # execution latency
        _sds((m,), np.float64),                      # tau_vec
        _sds((), np.float64),                        # horizon + drain cap
    )
    return fn, args, {}


def _cluster_chunk_key():
    from repro.core.clusterfast import _ClusterKey

    # Tiny but fully exercising key: 2 devices, 2 models, 2 exits, the
    # least-loaded dispatcher (drain-table backlog fold), a 2-arrival
    # burst, greedy single-rung ladder for caps 0..2.
    return _ClusterKey(
        num_devices=2, num_models=2, num_exits=2, max_queue=4, pad_len=8,
        chunk_steps=4, burst=2, max_batch=2, ladder=((0,), (1,), (2,)),
        allowed=(True, True), fallback_exit=0, clip=10.0, factored=True,
        dispatcher="least-loaded",
    )


def _build_cluster_step():
    import numpy as np
    from repro.core.clusterfast import _build_cluster_chunk_fn

    key = _cluster_chunk_key()
    fn = _build_cluster_chunk_fn(key)
    lanes = 2
    g, m, e, q, p = (key.num_devices, key.num_models, key.num_exits,
                     key.max_queue, key.pad_len)
    b1, r = key.max_batch + 1, len(key.ladder[0])
    carry = (
        _sds((lanes,), np.int32),                    # ai
        _sds((lanes, g, m, q), np.float64),          # qarr
        _sds((lanes, g, m, q), np.float64),          # qew
        _sds((lanes, g, m), np.int32),               # qhead
        _sds((lanes, g, m), np.int32),               # qlen
        _sds((lanes, g), np.float64),                # pend
        _sds((lanes, g), np.bool_),                  # inq
        _sds((lanes, g), np.bool_),                  # alive
        _sds((lanes, g), np.bool_),                  # done
        _sds((lanes, g), np.float64),                # clock
        _sds((lanes, g), np.float64),                # busy
        _sds((lanes,), np.int32),                    # rr
        _sds((lanes,), np.bool_),                    # blocked
        _sds((lanes,), np.bool_),                    # over
    )
    args = (
        carry,
        _sds((lanes, p), np.float64),                # arr_t
        _sds((lanes, p), np.int32),                  # arr_m
        _sds((lanes, p), np.float64),                # arr_ew
        _sds((g, m, b1, e, r), np.float64),          # lat_by_cap
        _sds((g, m, e, b1), np.float64),             # exec_lat
        _sds((g, m, q + 1), np.float64),             # drain_tab
        _sds((g, m), np.float64),                    # b1_final
        _sds((m,), np.float64),                      # tau_vec
        _sds((g, m), np.bool_),                      # placement mask
        _sds((), np.float64),                        # horizon + drain cap
        _sds((), np.float64),                        # failure barrier
    )
    return fn, args, {}


def _build_jnp_score():
    import numpy as np
    from repro.core.scoring import _jnp_score

    m, q, n = 3, 8, 6
    return _jnp_score, (
        _sds((m, q), np.float32), _sds((m, q), np.float32),
        _sds((n,), np.float32), _sds((n,), np.int32),
        _sds((n,), np.int32), _sds((), np.float32), _sds((), np.float32),
    ), {}


def _build_stability_kernel():
    import functools

    import numpy as np
    from repro.kernels.stability_score.kernel import stability_scores_kernel

    m, q, n = 4, 16, 12
    fn = functools.partial(
        stability_scores_kernel, tau=0.05, clip=10.0, block_m=8,
        interpret=True)
    return fn, (
        _sds((m, q), np.float32), _sds((m, q), np.float32),
        _sds((n,), np.float32), _sds((n,), np.int32), _sds((n,), np.int32),
    ), {}


PRECISION_ARTIFACTS: Tuple[ArtifactSpec, ...] = (
    ArtifactSpec(
        name="urgency.lattice_stability_scores",
        dtype_contract="float64",
        build=_build_lattice_scores,
        notes="Eq. 4-7 reference scoring: the oracle every backend and both "
              "engines are pinned against; any f32 here poisons everything "
              "downstream.",
    ),
    ArtifactSpec(
        name="simfast.scan_step[factored]",
        dtype_contract="float64",
        build=lambda: _build_scan_step(True),
        notes="the compiled serving round (factored-exponential scoring); "
              "bitwise-equal decisions/metrics vs the Python loop require "
              "pure float64.",
    ),
    ArtifactSpec(
        name="simfast.scan_step[direct]",
        dtype_contract="float64",
        build=lambda: _build_scan_step(False),
        notes="the compiled serving round on the direct Eq. 3 path (long-"
              "horizon fallback).",
    ),
    ArtifactSpec(
        name="clusterfast.scan_step[least-loaded]",
        dtype_contract="float64",
        build=_build_cluster_step,
        notes="the compiled cluster step (arrival burst + device round + "
              "dispatcher fold over [G,M,Q] rings); bitwise-equal decisions "
              "and metrics vs ClusterSimulator require pure float64 — the "
              "one-ulp idle poke and drain-table folds die in f32.",
    ),
    ArtifactSpec(
        name="scoring.jnp_backend",
        dtype_contract="float32",
        build=_build_jnp_score,
        x64=False,
        rtol=2e-4,
        notes="declared float32 tier (SchedulerConfig.backend='jnp'); "
              "decision-equivalence property-tested, score error bound "
              "enforced by the tolerance test.",
    ),
    ArtifactSpec(
        name="stability_score.kernel",
        dtype_contract="float32",
        build=_build_stability_kernel,
        x64=False,
        rtol=2e-4,
        notes="the Pallas kernel path downcasts cand_latency to float32 at "
              "the ops.py boundary (kernels/stability_score/ops.py) — "
              "declared here, bounded by the extreme-magnitude tolerance "
              "test in tests/test_analysis.py.",
    ),
)


# ---------------------------------------------------------------------------
# Layer 2: no-recompile guards
# ---------------------------------------------------------------------------


def _guard_stability_ops():
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels.stability_score.ops import stability_scores

    rng = np.random.default_rng(41)
    m, q = 3, 8
    w = jnp.asarray(np.sort(rng.uniform(0, 0.1, (m, q)))[:, ::-1].copy(),
                    jnp.float32)
    mask = jnp.ones((m, q), jnp.float32)
    lat = jnp.asarray(rng.uniform(1e-3, 2e-2, m), jnp.float32)
    bat = jnp.asarray(rng.integers(1, 5, m), jnp.int32)
    calls = [((w, mask, lat, bat),
              dict(tau=tau, clip=clip, interpret=True))
             for tau in (0.019, 0.02, 0.05, 0.1) for clip in (5.0, 10.0)]
    # per-task deadline matrices: same shape family, varying values
    for scale in (0.02, 0.04, 0.08):
        tau_m = jnp.asarray(
            rng.uniform(0.5, 1.5, (m, q)) * scale, jnp.float32)
        calls.append(((w, mask, lat, bat),
                      dict(tau=tau_m, clip=10.0, interpret=True)))
    return stability_scores, calls


def _guard_jnp_score():
    import numpy as np
    import jax.numpy as jnp
    from repro.core.scoring import _jnp_score

    rng = np.random.default_rng(42)
    m, q, n = 3, 8, 6
    w = jnp.asarray(rng.uniform(0, 0.1, (m, q)), jnp.float32)
    mask = jnp.ones((m, q), jnp.float32)
    lat = jnp.asarray(rng.uniform(1e-3, 2e-2, n), jnp.float32)
    bat = jnp.asarray(rng.integers(1, 4, n), jnp.int32)
    cq = jnp.asarray(rng.integers(0, m, n), jnp.int32)
    calls = [((w, mask, lat, bat, cq, jnp.float32(tau), jnp.float32(clip)),
              {})
             for tau in (0.02, 0.03, 0.05, 0.08) for clip in (5.0, 10.0)]
    return _jnp_score, calls


def _guard_scan_chunk():
    import jax
    import numpy as np
    from repro.core.simfast import _build_chunk_fn

    key = _scan_chunk_key()
    fn = _build_chunk_fn(key)
    lanes, m, e, p = 2, key.num_models, key.num_exits, key.pad_len
    b1, r = key.max_batch + 1, len(key.ladder[0])
    rng = np.random.default_rng(43)
    with jax.enable_x64(True):
        calls = []
        for tau in (0.05, 0.08, 0.12):
            for limit in (1.0, 2.0):
                arrivals = np.sort(rng.uniform(0, 0.5, (lanes, m, p)))
                arr = np.stack(
                    [arrivals, np.exp(-arrivals / tau)], axis=-1)
                carry = (
                    np.zeros(lanes), np.zeros((lanes, m), np.int32),
                    np.zeros(lanes), np.zeros(lanes, bool),
                    np.zeros(lanes, bool),
                )
                lat_by_cap = rng.uniform(1e-3, 2e-2, (m, b1, e, r))
                exec_lat = rng.uniform(1e-3, 2e-2, (m, e, b1))
                tau_vec = np.full(m, tau)
                calls.append(((carry, arr, lat_by_cap, exec_lat, tau_vec,
                               np.float64(limit)), {}))
    return fn, calls


RECOMPILE_GUARDS: Tuple[RecompileGuard, ...] = (
    RecompileGuard(
        name="stability_score.ops[tau/clip/deadline-matrix sweep]",
        build=_guard_stability_ops,
        notes="generalizes the PR 4 _cache_size test: SLO and clip sweeps "
              "(scalar and per-task matrix tau) must reuse one executable "
              "per shape family.",
    ),
    RecompileGuard(
        name="scoring._jnp_score[tau/clip sweep]",
        build=_guard_jnp_score,
        notes="every scheduler in a sweep shares this module-level jit; a "
              "recompile per SLO would serialize fig8-style sweeps.",
    ),
    RecompileGuard(
        name="simfast.chunk[tau/limit sweep]",
        build=_guard_scan_chunk,
        x64=True,
        notes="the compiled scan chunk is keyed only by _StaticKey; "
              "deadline and drain-cap values are traced operands.",
    ),
)


# ---------------------------------------------------------------------------
# Layer 3: kernel envelopes
# ---------------------------------------------------------------------------


def _kernel_flash_attention():
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels.flash_attention.kernel import flash_attention_kernel

    rng = np.random.default_rng(1)
    b, h, kh, s, d = 1, 4, 2, 512, 64
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, kh, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, kh, s, d)), jnp.float32)
    return flash_attention_kernel, (q, k, v), dict(causal=True)


def _kernel_decode_attention():
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels.decode_attention.kernel import decode_attention_kernel

    rng = np.random.default_rng(2)
    b, h, kh, s, d = 2, 4, 2, 1024, 64
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, kh, s, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, kh, s, d)), jnp.float32)
    lens = jnp.asarray(rng.integers(1, s + 1, b), jnp.int32)
    return decode_attention_kernel, (q, k, v, lens), {}


def _kernel_exit_head():
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels.exit_head.kernel import exit_head_kernel

    rng = np.random.default_rng(3)
    t, d, v = 256, 512, 4096
    h = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(d,)) * 0.1 + 1.0, jnp.float32)
    w = jnp.asarray(rng.normal(size=(d, v)) / np.sqrt(d), jnp.float32)
    return exit_head_kernel, (h, g, w), {}


def _kernel_rmsnorm():
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels.rmsnorm.kernel import rmsnorm_kernel

    rng = np.random.default_rng(4)
    t, d = 512, 2048
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(d,)) * 0.2 + 1.0, jnp.float32)
    return rmsnorm_kernel, (x, g), {}


def _kernel_stability_score():
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels.stability_score.kernel import stability_scores_kernel

    rng = np.random.default_rng(5)
    m, q, n = 4, 16, 12
    w = jnp.asarray(np.sort(rng.uniform(0, 0.1, (m, q)))[:, ::-1].copy(),
                    jnp.float32)
    mask = jnp.ones((m, q), jnp.float32)
    lat = jnp.asarray(rng.uniform(1e-3, 2e-2, n), jnp.float32)
    bat = jnp.asarray(rng.integers(1, 5, n), jnp.int32)
    cq = jnp.asarray(rng.integers(0, m, n), jnp.int32)
    return stability_scores_kernel, (w, mask, lat, bat, cq), dict(
        tau=0.05, clip=10.0, block_m=8)


KERNEL_SPECS: Tuple[KernelSpec, ...] = (
    KernelSpec(
        name="flash_attention",
        build=_kernel_flash_attention,
        notes="GQA causal prefill attention; audited at (1,4heads/2kv,512,"
              "64) with the default 256/512 blocks.",
    ),
    KernelSpec(
        name="decode_attention",
        build=_kernel_decode_attention,
        notes="split-K single-token decode over a 1024-entry cache; "
              "lengths ride in SMEM.",
    ),
    KernelSpec(
        name="exit_head",
        build=_kernel_exit_head,
        notes="fused norm+LM-head+confidence streaming a 4096-vocab slab "
              "in 1024-wide tiles.",
    ),
    KernelSpec(
        name="rmsnorm",
        build=_kernel_rmsnorm,
        notes="row-tiled, feature-resident at (512, 2048).",
    ),
    KernelSpec(
        name="stability_score",
        build=_kernel_stability_score,
        notes="the scheduler scoring kernel on a 12-candidate lattice over "
              "4 queues (pads N 12->16 for block_m=8).",
    ),
)
