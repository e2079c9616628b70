"""jaxpr auditor — dtype contracts, primitive hygiene, recompile guards.

Layer 2 of the static-analysis suite: the AST layer sees source text, but
the bitwise contract lives in what XLA actually compiles. This module
traces the artifacts named in the precision manifest to jaxprs and checks:

  * **dtype contract** — a ``float64``-contract artifact must not contain
    *any* float32/float16/bfloat16 value: no ``convert_element_type`` to a
    narrow float, no narrow constant, no narrow intermediate. (An implicit
    downcast is exactly how the bitwise guarantee dies silently: the op
    still runs, the numbers are just slightly wrong.)
  * **primitive denylist** — no host callbacks or debug prints inside hot
    paths (``pure_callback``/``io_callback``/``debug_callback``): they
    force host round-trips, break ``vmap``/donation assumptions, and make
    timing observable to the traced code.
  * **no-recompile guards** — generalizing the PR 4 ``_cache_size`` test:
    sweeping traced operands (tau / clip / deadline matrices / drain caps)
    through a compiled artifact must not grow its compile cache; a silent
    static-argification turns an O(1)-compile sweep into O(grid).

All checks recurse into nested jaxprs (pjit bodies, ``scan``/``while``/
``cond`` branches, custom-call subcomputations).
"""

from __future__ import annotations

import contextlib
import inspect
from typing import Any, Iterable, List, Optional, Sequence, Tuple

from repro.analysis.detlint import Finding
from repro.analysis import manifest as _manifest

__all__ = ["audit_jaxpr", "audit_artifact", "audit_precision_manifest",
           "no_recompile_findings", "audit_recompile_guards",
           "NARROW_FLOATS", "DENYLISTED_PRIMITIVES"]

NARROW_FLOATS = ("float32", "float16", "bfloat16")

DENYLISTED_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "debug_print", "host_callback", "outside_call",
})


def _subjaxprs(params: dict) -> Iterable[Any]:
    """Yield every jaxpr nested in an eqn's params (pjit/scan/cond/...)."""
    for value in params.values():
        vals = value if isinstance(value, (tuple, list)) else (value,)
        for v in vals:
            if hasattr(v, "jaxpr"):        # ClosedJaxpr
                yield v.jaxpr
            elif hasattr(v, "eqns"):       # raw Jaxpr
                yield v


def _walk_eqns(jaxpr) -> Iterable[Any]:
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _subjaxprs(eqn.params):
            yield from _walk_eqns(sub)


def _artifact_location(fn) -> Tuple[str, int]:
    """Best-effort source location for a traced artifact (for file:line
    output); falls back to the manifest itself."""
    target = fn
    for attr in ("func", "__wrapped__"):
        while hasattr(target, attr):
            target = getattr(target, attr)
    try:
        path = inspect.getsourcefile(target) or "<unknown>"
        _, line = inspect.getsourcelines(target)
        return path, line
    except (TypeError, OSError):
        return "src/repro/analysis/manifest.py", 1


def audit_jaxpr(jaxpr, *, name: str, dtype_contract: str = "float64",
                path: str = "<traced>", line: int = 1) -> List[Finding]:
    """Check one (closed) jaxpr against its declared dtype contract and the
    primitive denylist. Returns findings (empty == clean)."""
    inner = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    findings: List[Finding] = []
    seen_dtype: set = set()
    seen_prim: set = set()
    for eqn in _walk_eqns(inner):
        prim = eqn.primitive.name
        if prim in DENYLISTED_PRIMITIVES and prim not in seen_prim:
            seen_prim.add(prim)
            findings.append(Finding(
                "JXP002", path, line,
                f"artifact {name!r} contains denylisted primitive "
                f"{prim!r} (host callback / debug print in a hot path)",
                snippet=f"{name}::{prim}"))
        if dtype_contract == "float64":
            for var in eqn.outvars:
                dtype = getattr(getattr(var, "aval", None), "dtype", None)
                if dtype is not None and str(dtype) in NARROW_FLOATS:
                    sig = (prim, str(dtype))
                    if sig in seen_dtype:
                        continue
                    seen_dtype.add(sig)
                    findings.append(Finding(
                        "JXP001", path, line,
                        f"artifact {name!r} declares float64 but primitive "
                        f"{prim!r} produces {dtype} — a silent downcast on "
                        f"a bitwise-contract path",
                        snippet=f"{name}::{prim}->{dtype}"))
    if dtype_contract == "float64" and hasattr(jaxpr, "consts"):
        for const in jaxpr.consts:
            dtype = str(getattr(const, "dtype", ""))
            if dtype in NARROW_FLOATS:
                findings.append(Finding(
                    "JXP001", path, line,
                    f"artifact {name!r} declares float64 but closes over a "
                    f"{dtype} constant",
                    snippet=f"{name}::const->{dtype}"))
                break
    return findings


def audit_artifact(spec) -> List[Finding]:
    """Trace one manifest :class:`~repro.analysis.manifest.ArtifactSpec`
    and audit the resulting jaxpr."""
    import jax

    ctx = jax.enable_x64(True) if spec.x64 else contextlib.nullcontext()
    with ctx:
        fn, args, kwargs = spec.build()
        try:
            jaxpr = jax.make_jaxpr(fn)(*args, **kwargs)
        except Exception as e:  # tracing failure is itself a finding
            path, line = _artifact_location(fn)
            return [Finding(
                "JXP000", path, line,
                f"artifact {spec.name!r} failed to trace: {e}",
                snippet=f"{spec.name}::trace-error")]
    path, line = _artifact_location(fn)
    return audit_jaxpr(jaxpr, name=spec.name,
                       dtype_contract=spec.dtype_contract,
                       path=path, line=line)


def audit_precision_manifest(
    artifacts: Optional[Sequence] = None,
) -> List[Finding]:
    """Audit every artifact in the precision manifest (or an injected
    list — tests use this to prove a polluted artifact is caught)."""
    if artifacts is None:
        artifacts = _manifest.PRECISION_ARTIFACTS
    findings: List[Finding] = []
    for spec in artifacts:
        findings.extend(audit_artifact(spec))
    return findings


# ---------------------------------------------------------------------------
# no-recompile guards
# ---------------------------------------------------------------------------


def _cache_size_of(fn) -> Optional[int]:
    probe = getattr(fn, "_cache_size", None)
    if probe is not None:
        return probe()
    # functools.partial over a jitted fn
    inner = getattr(fn, "func", None)
    if inner is not None and hasattr(inner, "_cache_size"):
        return inner._cache_size()
    return None


def no_recompile_findings(guard) -> List[Finding]:
    """Run one :class:`~repro.analysis.manifest.RecompileGuard` sweep.

    The first call primes the compile cache (new shape families are
    legitimate compiles); every subsequent call must hit it. Returns a
    finding if the cache grew after priming, or if the target exposes no
    cache to measure (a guard silently measuring nothing is itself a bug).
    """
    import contextlib as _ctx

    import jax

    ctx = jax.enable_x64(True) if getattr(guard, "x64", False) else (
        _ctx.nullcontext())
    with ctx:
        fn, calls = guard.build()
        if not calls:
            return [Finding(
                "JXP003", "src/repro/analysis/manifest.py", 1,
                f"recompile guard {guard.name!r} produced no calls",
                snippet=f"{guard.name}::empty")]
        # Prime every distinct (shape, structure) family: sweeps are allowed
        # one compile per family, never one per value.
        primed: set = set()
        pending = []
        for args, kwargs in calls:
            key = _call_signature(args, kwargs)
            if key not in primed:
                primed.add(key)
                fn(*args, **kwargs)
            else:
                pending.append((args, kwargs))
        before = _cache_size_of(fn)
        if before is None:
            return [Finding(
                "JXP003", "src/repro/analysis/manifest.py", 1,
                f"recompile guard {guard.name!r}: target exposes no "
                f"_cache_size (not a jitted function?)",
                snippet=f"{guard.name}::no-cache")]
        for args, kwargs in pending:
            fn(*args, **kwargs)
        after = _cache_size_of(fn)
    if after > before:
        path, line = _artifact_location(fn)
        return [Finding(
            "JXP003", path, line,
            f"recompile guard {guard.name!r}: compile cache grew "
            f"{before}->{after} across a traced-operand sweep (a value "
            f"became static; sweeps now recompile per value)",
            snippet=f"{guard.name}::recompiled")]
    return []


def _call_signature(args, kwargs) -> tuple:
    """Shape/dtype/structure fingerprint of one call (value-independent)."""

    def leaf(x):
        shape = getattr(x, "shape", None)
        if shape is not None:
            return ("arr", tuple(shape), str(getattr(x, "dtype", "?")))
        return ("lit", type(x).__name__)

    return (tuple(leaf(a) for a in args),
            tuple(sorted((k, leaf(v)) for k, v in kwargs.items())))


def audit_recompile_guards(guards: Optional[Sequence] = None) -> List[Finding]:
    if guards is None:
        guards = _manifest.RECOMPILE_GUARDS
    findings: List[Finding] = []
    for guard in guards:
        findings.extend(no_recompile_findings(guard))
    return findings
