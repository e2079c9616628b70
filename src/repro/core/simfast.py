"""Compiled serving simulator: one run = one jitted ``lax.scan``.

The reference event loop (``repro.core.simulator.ServingSimulator``) is pure
Python: the accelerated scoring backends speed up one call inside a slow
interpreter loop, and sweep parallelism is process-level. This module
refactors a whole serving run into fixed-shape array state so it compiles:

  * per-model arrival times become one ``[M, P]`` float64 array, sorted and
    padded with ``+inf``; a FIFO queue is then just the contiguous window
    ``[served_m, served_m + qlen_m)`` of that array, so ingest is a count of
    window entries ``<= t`` and the queue's wait vector is one
    ``dynamic_slice`` of static width ``max_queue``;
  * the profile tables become dense ``[M, E, B_max+1]`` latency arrays
    (scheduler belief and execution ground truth separately, so
    ``sched_table`` / ``model_map`` deployment mixes work unchanged);
  * the batch ladder (Eq. 5 / the lattice generalisation) becomes a static
    ``[B_max+1, R]`` rung table built by calling the *actual* scheduler's
    ``batch_candidates`` for every possible cap — greedy, lattice, custom
    ladders and the bs=1 ablation all compile through one code path;
  * one scheduling round (ingest -> enumerate the (m, e, B) lattice ->
    Eq. 6 exit per candidate -> Sec. V-C / Eq. 4 scoring -> Eq. 7 argmin
    with the reference tiebreak -> pop batch, advance clock) is one
    ``lax.scan`` step; idle rounds are folded into the following dispatch
    (the reference's idle-advance is always followed by an ingest), so the
    scan length is bounded by the dispatch count, not the event count;
    ``jax.vmap`` lays independent traces (seeds x rates) side by side and
    ``jit`` compiles the whole run.

Everything runs in float64 (``jax.enable_x64(True)``): the clock
evolves by the *identical* IEEE operations as the Python loop (``t + L``,
``nextafter``), so dispatch/finish timestamps are bitwise-equal and
decisions stay equivalent — stability scores differ only at the ~ulp level
(summation order; and the fast scoring path below), which the Eq. 7 argmin
is insensitive to outside exact structural ties, where both engines apply
the identical (score, w_max, candidate order) tiebreak.

Scoring runs in one of two modes, selected automatically:

  * **factored** (the fast path): Eq. 3 urgency obeys
    ``exp((t + L - a)/tau - 1) = exp((t + L)/tau - 1) * exp(-a/tau)``, so
    the per-*task* exponential ``E = exp(-a/tau)`` is precomputed once per
    run outside the loop and each scan step pays only ``[N, M]`` scalar
    exponentials instead of ``[N, M, max_queue]`` — the difference between
    the step being exp-bound and being memory-bound. The factorisation is
    used only when ``max(arrival)/min(tau) <= 700``, where ``E`` stays a
    normal float64 (clips of overflowed products are exact, so late drains
    are safe; an underflowed ``E`` would not be).
  * **direct** (the reference formula ``lattice_stability_scores``, shared
    with the scoring backends): used for long-horizon / tight-deadline runs
    outside the factored range, and forceable via ``factored=False`` for
    A/B testing. Both modes are pinned against the Python engine by
    ``tests/test_simfast.py``.

Deliberately unsupported (rejected loudly, never approximated): schedulers
outside the Algorithm-1 family (Symphony's prune/next_wake, LQF/EDF),
non-default scoring backends, service-time noise, device drift, online
adaptation, and per-request deadlines that vary within a model's queue
(trace replay). The Python loop remains the reference for those; see
docs/simulator.md "Compiled fast path".
"""

from __future__ import annotations

import dataclasses
import functools
import operator
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.baselines import AllFinalDeadlineAwareScheduler, NoBatchingScheduler
from repro.core.metrics import summarize_arrays
from repro.core.profile import ProfileTable
from repro.core.request import Completion, Decision, Request, ServingTrace
from repro.core.workloads import TraceColumns
from repro.core.scheduler import (
    EdgeServingScheduler,
    LatticeEdgeServingScheduler,
    Scheduler,
    VectorizedEdgeServingScheduler,
)
from repro.core.simulator import SimResult
from repro.core.telemetry import DecisionRecord, Tracer
from repro.core.urgency import lattice_stability_scores

__all__ = ["ScanEngineUnsupported", "simulate_scan", "simulate_scan_batch"]


class ScanEngineUnsupported(NotImplementedError):
    """A feature the compiled engine does not reproduce bit-for-bit.

    The scan path refuses rather than approximates: silent semantic drift
    in a compiled rewrite of a discrete-event simulator is exactly what the
    equivalence suite exists to prevent. Fall back to the Python engine
    (``SweepSpec.engine="python"`` / ``ServingSimulator``) for these."""


# The Algorithm-1 family whose decisions the scan step reproduces: shared
# Eq. 5/6 candidate enumeration + stability-score argmin, no prune, no
# next_wake. Exact types, not isinstance: an unknown subclass may override
# decide()/batch_candidates() in ways the compiled step knows nothing about.
_SUPPORTED_SCHEDULERS = (
    EdgeServingScheduler,
    VectorizedEdgeServingScheduler,
    LatticeEdgeServingScheduler,
    AllFinalDeadlineAwareScheduler,
    NoBatchingScheduler,
)

_MAX_QUEUE_DEFAULT = 64  # initial window; doubled (with a recompile) on overflow
_FACTORED_RANGE = 700.0  # max(arrival)/min(tau) bound keeping exp(-a/tau) normal


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


@dataclasses.dataclass(frozen=True)
class _StaticKey:
    """Everything that shapes the compiled step (hashable jit-cache key)."""

    num_models: int
    num_exits: int
    max_queue: int
    pad_len: int          # P: padded per-model arrival-array length
    chunk_steps: int      # S: lax.scan length per launch
    max_batch: int
    ladder: Tuple[Tuple[int, ...], ...]   # [B_max+1][R] batch rungs (0 = pad)
    allowed: Tuple[bool, ...]             # [E] allowed-exit mask
    fallback_exit: int                    # shallowest allowed exit (Eq. 6)
    clip: float
    factored: bool        # factored-exponential scoring vs direct Eq. 3
    emit_aux: bool        # also record predicted latency + score per round


@functools.lru_cache(maxsize=64)
def _build_chunk_fn(key: _StaticKey):
    """Compile one scan chunk: every lane advances ``chunk_steps`` rounds.
    Returns (carry', ys) with ys stacked step-major."""
    M, E, Q = key.num_models, key.num_exits, key.max_queue
    ladder = jnp.asarray(np.array(key.ladder, dtype=np.int32))      # [B+1, R]
    R = int(ladder.shape[1])
    N = M * R
    allowed = jnp.asarray(np.array(key.allowed, dtype=bool))        # [E]
    e0 = key.fallback_exit
    clip = key.clip
    m_idx = jnp.arange(M)
    n_idx = jnp.arange(N)
    cand_queue = jnp.repeat(m_idx, R)                               # [N]
    pos_q = jnp.arange(Q)[None, :]                                  # [1, Q]

    def run_chunk(carry, arr, lat_by_cap, exec_lat, tau_vec, limit):
        # carry: (t, served[M], busy, done, overflow) for one lane.
        # arr: [M, P, 2] of (arrival time, exp(-arrival/tau)) rows, sorted
        #      by arrival, +inf / 0.0 padded.
        # lat_by_cap: [M, B_max+1, E, R] scheduler-belief latency per
        #      (queue, queue-length cap, exit, ladder rung), prebuilt on the
        #      host so candidate enumeration is one row gather per queue.
        # exec_lat: [M, E, B_max+1] ground-truth execution latency.
        # tau_vec: [M] effective per-model deadline (Eq. 6 + scoring).

        def step(c, _):
            t0, served, busy, done, overflow = c

            # FIFO queue content is the contiguous range [served, served +
            # qlen) of the sorted arrival array, so one width-(Q+1) window
            # holds every queued task plus the next future arrival; counting
            # window entries <= t *is* the reference loop's ingest cursor
            # (t is monotone). A count of Q+1 means the queue outgrew the
            # window and the host must retry wider.
            win = jax.vmap(
                lambda row, s: lax.dynamic_slice(
                    row, (s, jnp.zeros((), jnp.int32)), (Q + 1, 2)
                )
            )(arr, served)                                          # [M, Q+1, 2]
            arr_win = win[:, :, 0]                                  # [M, Q+1]
            qlen0 = jnp.sum(arr_win <= t0, axis=1).astype(jnp.int32)

            # Idle rounds fold into the dispatch that always follows them:
            # when every queue is empty, the reference sleeps to the next
            # arrival with one-ulp strict progress (t = nextafter(max(t,
            # next), inf)), ingests it, and dispatches. No serve happened,
            # so the same window just gets recounted at the advanced clock.
            nxt = jnp.min(jnp.where(arr_win > t0, arr_win, jnp.inf))
            empty0 = ~jnp.any(qlen0 > 0)
            t_idle = jnp.nextafter(jnp.maximum(t0, nxt), jnp.inf)
            halt = empty0 & ~jnp.isfinite(nxt)           # no work ever again
            t = jnp.where(empty0 & ~halt, t_idle, t0)    # halt: break pre-advance
            over_cap = empty0 & (t > limit)              # idle past drain cap
            qlen_raw = jnp.sum(arr_win <= t, axis=1).astype(jnp.int32)
            overflow = overflow | jnp.any(qlen_raw > Q)
            qlen_c = jnp.minimum(qlen_raw, Q)

            mask_b = pos_q < qlen_c[:, None]                        # [M, Q]
            # Oldest wait per queue, zero when empty, exactly like
            # QueueSnapshot.w_max.
            w_max = jnp.where(qlen_c > 0, t - arr_win[:, 0], 0.0)   # [M]

            # Candidate lattice: one rung row per queue from the static
            # ladder (queue asc, batch desc — the reference enumeration
            # order), Eq. 6 deepest-feasible exit per rung.
            cap = jnp.minimum(qlen_c, key.max_batch)                # [M]
            batches = ladder[cap]                                   # [M, R]
            valid = (batches > 0).reshape(-1)                       # [N]
            lat_sel = jnp.take_along_axis(
                lat_by_cap, cap[:, None, None, None], axis=1
            )[:, 0]                                                 # [M, E, R]
            feas = (
                (w_max[:, None, None] + lat_sel <= tau_vec[:, None, None])
                & allowed[None, :, None]
            )
            e_axis = jnp.arange(E)[None, :, None]
            deepest = jnp.max(jnp.where(feas, e_axis, -1), axis=1)  # [M, R]
            e_sel = jnp.where(deepest >= 0, deepest, e0)
            lat_cand = jnp.sum(
                jnp.where(e_sel[:, None, :] == e_axis, lat_sel, 0.0), axis=1
            )                                                       # [M, R]

            cand_batch = batches.reshape(-1)                        # [N]
            cand_lat = lat_cand.reshape(-1)                         # [N]

            if key.factored:
                # Eq. 3/4 + Sec. V-C with the per-task exponential factored
                # out: urgency(w + L) = min(A * E, C) with A = exp((t + L) /
                # tau - 1) per (candidate, queue) and E = exp(-a/tau) per
                # task, precomputed — [N, M] exponentials per round instead
                # of [N, M, max_queue]; the remaining [N, M, Q] work is one
                # fused multiply/min/mask pass (amp=inf on deep drains is
                # benign: the where() masks the inf*0 pad NaNs, real tasks
                # clip to C exactly).
                ew = win[:, :Q, 1]                                  # [M, Q]
                amp = jnp.exp(
                    (t + cand_lat[:, None]) / tau_vec[None, :] - 1.0
                )                                                   # [N, M]
                urg = jnp.where(
                    mask_b[None, :, :],
                    jnp.minimum(amp[:, :, None] * ew[None, :, :], clip),
                    0.0,
                )                                                   # [N, M, Q]
                total = jnp.sum(urg, axis=(1, 2))
                own = urg[n_idx, cand_queue, :]                     # [N, Q]
                removed = jnp.sum(
                    jnp.where(pos_q < cand_batch[:, None], own, 0.0), axis=1
                )
                scores = total - removed
            else:
                w = jnp.where(mask_b, t - arr_win[:, :Q], 0.0)
                mask = mask_b.astype(jnp.float64)
                scores = lattice_stability_scores(
                    w, mask, cand_lat, cand_batch, cand_queue,
                    tau_vec[:, None], clip,
                )

            # Eq. 7 argmin with the reference tiebreak: min score, then max
            # w_max, then first candidate (np.lexsort is stable).
            scores_v = jnp.where(valid, scores, jnp.inf)
            best = jnp.min(scores_v)
            wm_c = jnp.repeat(w_max, R)
            tie = valid & (scores_v == best)
            wm_best = jnp.max(jnp.where(tie, wm_c, -jnp.inf))
            pick = jnp.argmax(tie & (wm_c == wm_best))
            has_work = jnp.any(valid)

            m_star = cand_queue[pick]
            e_star = e_sel.reshape(-1)[pick]
            b_star = cand_batch[pick]
            service = exec_lat[m_star, e_star, b_star]
            t_end = t + service

            active = ~done
            is_disp = active & has_work & ~over_cap
            t_new = jnp.where(is_disp, t_end, jnp.where(active, t, t0))
            pop = jnp.where(is_disp, b_star, 0).astype(jnp.int32)
            served_new = served + jnp.where(m_idx == m_star, pop, 0)
            busy_new = busy + jnp.where(is_disp, service, 0.0)
            # The reference breaks *after* advancing t past horizon +
            # drain_cap in the dispatch branch (the over-cap quantum still
            # counts) and *before* dispatching in the idle branch.
            done_new = done | halt | over_cap | (is_disp & (t_end > limit))
            done_new = done_new | overflow  # window wrong: stop, host retries

            # One int32 codes the whole round: -1 = no dispatch, else
            # m + M*(e + E*b). Finish times and predicted latencies are
            # bitwise-recomputable on the host from (m, e, b) and t0.
            code = jnp.where(
                is_disp, m_star + M * (e_star + E * b_star), -1
            ).astype(jnp.int32)
            if key.emit_aux:
                # Decision margin: runner-up candidate score minus the
                # winner's (inf with a single candidate, 0 on an exact
                # tie) — same definition as telemetry.decision_margin's
                # second-smallest-minus-smallest on the host.
                runner_up = jnp.min(jnp.where(n_idx == pick, jnp.inf,
                                              scores_v))
                ys = (code, t, scores[pick], runner_up - best)
            else:
                ys = (code, t)
            return (t_new, served_new, busy_new, done_new, overflow), ys

        return lax.scan(step, carry, None, length=key.chunk_steps, unroll=4)

    fn = jax.vmap(
        run_chunk, in_axes=((0, 0, 0, 0, 0), 0, None, None, None, None)
    )
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# Host-side packing and validation
# ---------------------------------------------------------------------------


def _validate_scheduler(scheduler: Scheduler) -> None:
    if type(scheduler) not in _SUPPORTED_SCHEDULERS:
        raise ScanEngineUnsupported(
            f"scan engine supports only the Algorithm-1 scheduler family "
            f"{sorted(c.__name__ for c in _SUPPORTED_SCHEDULERS)}; got "
            f"{type(scheduler).__name__!r} (Symphony's prune/next_wake and "
            f"the LQF/EDF baselines need the Python engine)"
        )
    if scheduler.scoring.name != "numpy":
        raise ScanEngineUnsupported(
            f"scan engine compiles its own scoring pass; the "
            f"backend={scheduler.scoring.name!r} knob only applies to the "
            f"Python engine — use the default backend='numpy'"
        )


@dataclasses.dataclass
class _Lane:
    """One arrival trace, unpacked into per-model columnar arrays."""

    requests: Sequence[Request]
    model: np.ndarray      # [n] queue index per request, arrival order
    arrival: np.ndarray    # [n] arrival times, sorted
    by_model: List[np.ndarray]   # per-model index lists into the trace
    tau_vec: np.ndarray    # [M] effective per-model deadline


def _unpack_lane(
    arrivals, num_models: int, slo: float
) -> _Lane:
    n = len(arrivals)
    if isinstance(arrivals, TraceColumns):
        # Columnar lane: already the arrays this function exists to build.
        model = arrivals.model
        arrival = arrivals.arrival
    else:
        # map(attrgetter) keeps attribute extraction in C: this runs once
        # per request per run, so it is the scan engine's host-side hot loop.
        model = np.fromiter(
            map(operator.attrgetter("model"), arrivals),
            dtype=np.int64, count=n,
        )
        arrival = np.fromiter(
            map(operator.attrgetter("arrival"), arrivals),
            dtype=np.float64,
            count=n,
        )
    if n and np.any(np.diff(arrival) < 0):
        raise ValueError("arrivals must be sorted by arrival time")
    if n and (model.min() < 0 or model.max() >= num_models):
        raise ValueError(
            f"arrival trace targets model {model.max()}, but the "
            f"simulation has {num_models} queues"
        )
    tau_vec = np.full(num_models, slo, dtype=np.float64)
    by_model = [np.flatnonzero(model == m) for m in range(num_models)]
    if isinstance(arrivals, TraceColumns):
        deadline = arrivals.deadline          # [n] with NaN = None, or None
    else:
        deadline = None
        distinct = set(map(operator.attrgetter("deadline"), arrivals))
        if distinct and distinct != {None}:
            deadline = np.fromiter(
                (np.nan if r.deadline is None else r.deadline
                 for r in arrivals),
                dtype=np.float64,
                count=n,
            )
    if deadline is not None:
        # Per-request deadlines present: supported iff constant per model.
        for m in range(num_models):
            d = deadline[by_model[m]]
            if len(d) == 0:
                continue
            has = ~np.isnan(d)
            if has.any():
                vals = np.unique(d[has])
                if len(vals) > 1 or not has.all():
                    raise ScanEngineUnsupported(
                        f"model {m} carries per-request deadlines that vary "
                        f"within its queue; the scan engine supports only "
                        f"per-model constant deadlines (trace replay with "
                        f"arbitrary deadline mixes needs the Python engine)"
                    )
                tau_vec[m] = float(vals[0])
    return _Lane(arrivals, model, arrival, by_model, tau_vec)


def _dense_latency(
    table: ProfileTable, rows: Sequence[int], num_exits: int, max_batch: int
) -> np.ndarray:
    """[M, E, B_max+1] lookup array via the table's own clamped ``__call__``
    (slot 0 is never dispatched; fill with batch 1 to stay finite)."""
    out = np.empty((len(rows), num_exits, max_batch + 1), dtype=np.float64)
    for i, row in enumerate(rows):
        for e in range(num_exits):
            out[i, e, 0] = table(row, e, 1)
            for b in range(1, max_batch + 1):
                out[i, e, b] = table(row, e, b)
    return out


def _build_ladder(scheduler: Scheduler, max_batch: int) -> Tuple[Tuple[int, ...], ...]:
    """[B_max+1][R] rung table from the scheduler's own ``batch_candidates``
    (cap -> descending rungs, 0-padded): greedy, lattice, custom ladders and
    the bs=1 ablation all serialise into one static array."""
    rows = [tuple(scheduler.batch_candidates(cap)) for cap in range(max_batch + 1)]
    width = max((len(r) for r in rows), default=1) or 1
    return tuple(r + (0,) * (width - len(r)) for r in rows)


def _pack_lanes(
    lanes: Sequence[_Lane], num_models: int, pad_len: int, factored: bool
) -> np.ndarray:
    """[L, M, P, 2] of (arrival, exp(-arrival/tau)) rows, +inf / 0.0 padded
    (the pad's exponential factor is exactly the +inf arrival's)."""
    out = np.empty((len(lanes), num_models, pad_len, 2), dtype=np.float64)
    out[:, :, :, 0] = np.inf
    out[:, :, :, 1] = 0.0
    for li, lane in enumerate(lanes):
        for m in range(num_models):
            a = lane.arrival[lane.by_model[m]]
            out[li, m, : len(a), 0] = a
            if factored:
                out[li, m, : len(a), 1] = np.exp(-a / lane.tau_vec[m])
    return out


# ---------------------------------------------------------------------------
# Result reconstruction (vectorised numpy, no per-request Python loop)
# ---------------------------------------------------------------------------


def _reconstruct(
    ys: "dict[str, np.ndarray]",
    lane: _Lane,
    table: ProfileTable,
    sched_lat: np.ndarray,
    exec_lat: np.ndarray,
    num_exits: int,
    horizon: float,
    warmup_tasks: int,
    model_map: Optional[Sequence[int]],
    busy: float,
    t_final: float,
    keep_completions: bool,
    keep_traces: bool,
    tracer: Optional[Tracer] = None,
    slo: float = 0.050,
) -> SimResult:
    M = len(lane.tau_vec)
    code = ys["code"]
    disp = code >= 0
    dcode = code[disp]
    dm = dcode % M
    rest = dcode // M
    de = rest % num_exits
    db = rest // num_exits
    dt0 = ys["t0"][disp]
    # t_end = t + L(m, e, B) is the identical IEEE add the scan performed,
    # so recomputing it here is bitwise-faithful to the in-scan clock.
    dt1 = dt0 + exec_lat[dm, de, db]
    n_arr = len(lane.model)
    # Reference completion order is: dispatch rounds in time order, FIFO
    # within each batch. Both coordinates are directly computable -- no
    # sort needed. The k-th dispatch of model m serves the next
    # ``db`` requests of m's arrival-ordered queue, so the per-model
    # position of each completion is (batches m served before this
    # dispatch) + (offset within this batch).
    D = len(dm)
    if D:
        db64 = db.astype(np.int64)
        gidx = np.repeat(np.arange(D), db64)
        starts = np.cumsum(db64) - db64
        off = np.arange(len(gidx)) - starts[gidx]   # 0..b-1, FIFO in batch
        prior = np.empty(D, dtype=np.int64)         # m's served-before count
        for m in range(M):
            sel = dm == m
            bm = np.where(sel, db64, 0)
            prior[sel] = (np.cumsum(bm) - bm)[sel]
        # trace index per completion, via the concatenated per-model lists
        bm_flat = np.concatenate(lane.by_model) if M else np.array([], np.int64)
        bm_off = np.zeros(M, dtype=np.int64)
        np.cumsum([len(ix) for ix in lane.by_model[:-1]], out=bm_off[1:])
        model = dm[gidx]
        ridx = bm_flat[bm_off[model] + prior[gidx] + off]
        exits = de[gidx].astype(np.int64)
        batches = db64[gidx]
        arrival = lane.arrival[ridx]
        dispatch = dt0[gidx]
        finish = dt1[gidx]
        tau = lane.tau_vec[model]
    else:
        model = exits = batches = ridx = np.array([], dtype=np.int64)
        arrival = dispatch = finish = tau = np.array([], dtype=np.float64)

    n_completed = len(model)
    residual = n_arr - n_completed
    span = max(t_final, horizon)
    metrics = summarize_arrays(
        models=model,
        exits=exits,
        batches=batches,
        latencies=finish - arrival,
        queueings=dispatch - arrival,
        taus=tau,
        table=table,
        warmup_tasks=warmup_tasks,
        busy_time=busy,
        span=span,
        residual_queue=residual,
        model_map=model_map,
        dropped=0,
    )

    completions: List[Completion] = []
    if keep_completions and n_completed:
        for i in range(n_completed):
            req = lane.requests[int(ridx[i])]
            completions.append(Completion(
                req_id=req.req_id,
                model=int(model[i]),
                arrival=req.arrival,
                dispatch=float(dispatch[i]),
                finish=float(finish[i]),
                exit_idx=int(exits[i]),
                batch_size=int(batches[i]),
                deadline=req.deadline,
            ))

    traces: List[ServingTrace] = []
    if keep_traces:
        dplat = sched_lat[dm, de, db]
        dscore = ys["score"][disp]
        for i in range(len(dm)):
            traces.append(ServingTrace(
                t_start=float(dt0[i]),
                t_end=float(dt1[i]),
                decision=Decision(
                    model=int(dm[i]),
                    exit_idx=int(de[i]),
                    batch_size=int(db[i]),
                    predicted_latency=float(dplat[i]),
                    stability_score=float(dscore[i]),
                ),
                queue_lengths=(),
            ))

    trace = None
    if tracer is not None:
        # Host-side timeline reconstruction from the packed decision codes.
        # Everything but score/margin is recomputed by the *identical* IEEE
        # ops the Python engine's snapshot performs, so the timeline is
        # bitwise-equal to the reference trace (property-tested):
        #   depth_m  = |arrivals_m <= t| - served_before_m   (ingest rule)
        #   age_m    = t - arrival_of_oldest_queued          (w_max rule)
        D = len(dm)
        db64d = db.astype(np.int64)
        depths = np.zeros((D, M), dtype=np.int64)
        ages = np.zeros((D, M), dtype=np.float64)
        for m in range(M):
            arr_m = lane.arrival[lane.by_model[m]]
            bm = np.where(dm == m, db64d, 0)
            served_before = np.cumsum(bm) - bm
            cnt = np.searchsorted(arr_m, dt0, side="right")
            depth_m = cnt - served_before
            depths[:, m] = depth_m
            if len(arr_m):
                head = np.minimum(served_before, len(arr_m) - 1)
                ages[:, m] = np.where(depth_m > 0, dt0 - arr_m[head], 0.0)
        scores_d = ys["score"][disp]
        margins_d = ys["margin"][disp]
        dplat = sched_lat[dm, de, db]
        for k in range(D):
            tracer.decisions.append(DecisionRecord(
                t=float(dt0[k]), device=0, model=int(dm[k]),
                exit_idx=int(de[k]), batch_size=int(db[k]),
                predicted_latency=float(dplat[k]), t_end=float(dt1[k]),
                score=float(scores_d[k]), margin=float(margins_d[k]),
                queue_depths=tuple(int(x) for x in depths[k]),
                oldest_ages=tuple(float(x) for x in ages[k]),
            ))
        for i in range(n_completed):
            req = lane.requests[int(ridx[i])]
            tracer.record_completion(
                req, float(dispatch[i]), float(finish[i]),
                int(exits[i]), int(batches[i]), slo)
        served_total = np.zeros(M, dtype=np.int64)
        np.add.at(served_total, dm, db64d)
        for m in range(M):
            for j in lane.by_model[m][served_total[m]:]:
                tracer.record_residual(lane.requests[int(j)], slo,
                                       device=-1)
        trace = tracer.freeze(
            engine="scan", num_models=M, num_devices=1, slo=slo,
            horizon=horizon, span=span, warmup_used=metrics.warmup_used,
            n_arrivals=n_arr)
    return SimResult(metrics, completions, traces, span, trace=trace)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------


def simulate_scan_batch(
    scheduler: Scheduler,
    table: ProfileTable,
    arrival_lanes: Sequence[Sequence[Request]],
    horizon: float,
    num_models: Optional[int] = None,
    warmup_tasks: int = 100,
    model_map: Optional[Sequence[int]] = None,
    drain_cap: float = 600.0,
    max_queue: Optional[int] = None,
    keep_completions: bool = False,
    keep_traces: bool = False,
    factored: Optional[bool] = None,
    tracers: Optional[Sequence[Optional[Tracer]]] = None,
) -> List[SimResult]:
    """Run one serving experiment per arrival lane, all lanes side by side
    in a single jitted, vmapped ``lax.scan`` (seeds x rates in one XLA
    launch). All lanes share the scheduler config and tables; only the
    traces differ. Returns one :class:`SimResult` per lane, in order.

    The scan runs in fixed-size compiled chunks with a host-side
    completion check between launches, so a grid of light lanes does not
    pay the worst-case step bound of its heaviest lane. If any lane's
    queue outgrows the ``max_queue`` window the whole batch retries with
    the window doubled (one recompile; results are never truncated).
    ``factored=None`` auto-selects the factored-exponential scoring path
    whenever its float64 range condition holds (see module docstring).

    ``tracers`` (optional, one ``telemetry.Tracer`` or ``None`` per lane)
    turns on telemetry: the scan emits its score/margin aux and the host
    reconstructs each traced lane's full decision timeline and request
    spans from the packed codes — bitwise-equal to the Python engine's
    trace on everything but score/margin (ulp-level, see telemetry docs).
    Tracing never changes the compiled step's decisions or the metrics.
    """
    _validate_scheduler(scheduler)
    M = num_models or scheduler.table.num_models
    cfg = scheduler.config
    lanes = [_unpack_lane(lane, M, cfg.slo) for lane in arrival_lanes]
    if not lanes:
        return []
    if tracers is None:
        tracers = [None] * len(lanes)
    assert len(tracers) == len(lanes), "one tracer slot per lane"
    for tr in tracers:
        if tr is not None:
            tr.reset()
    any_tracer = any(tr is not None for tr in tracers)
    tau_vec = lanes[0].tau_vec
    for lane in lanes[1:]:
        if not np.array_equal(lane.tau_vec, tau_vec):
            raise ScanEngineUnsupported(
                "all lanes of one scan batch must share the same per-model "
                "deadline vector (split differing lanes into separate calls)"
            )

    n_max = max(
        (max((len(ix) for ix in lane.by_model), default=0) for lane in lanes),
        default=0,
    )
    n_total_max = max((len(lane.model) for lane in lanes), default=0)
    last_arrival = max(
        (lane.arrival[-1] for lane in lanes if len(lane.arrival)),
        default=0.0,
    )
    if factored is None:
        factored = bool(last_arrival / tau_vec.min() <= _FACTORED_RANGE)
    E = scheduler.table.num_exits
    Bmax = cfg.max_batch
    ladder = _build_ladder(scheduler, Bmax)
    allowed = tuple(e in scheduler._exits for e in range(E))
    rows = (
        [model_map[m] for m in range(M)] if model_map is not None
        else list(range(M))
    )
    sched_lat = _dense_latency(scheduler.table, list(range(M)), E, Bmax)
    exec_lat = _dense_latency(table, rows, E, Bmax)
    # [M, cap, E, R]: the candidate lattice's latencies per queue-length
    # cap, so in-scan enumeration is one take_along_axis over cap.
    ladder_np = np.array(ladder, dtype=np.int64)
    lat_by_cap = np.ascontiguousarray(
        sched_lat[:, :, ladder_np].transpose(0, 2, 1, 3)
    )
    limit = horizon + drain_cap
    # Idle rounds fold into dispatches, so rounds <= dispatches + 2 and
    # every dispatch serves >= 1 request.
    budget = n_total_max + 4

    Q = max_queue or min(_MAX_QUEUE_DEFAULT, _pow2(max(n_max, 1)))
    while True:
        P = _pow2(n_max + Q + 2)
        S = min(_pow2(budget), 1024)
        key = _StaticKey(
            num_models=M, num_exits=E, max_queue=Q, pad_len=P,
            chunk_steps=S, max_batch=Bmax, ladder=ladder, allowed=allowed,
            fallback_exit=scheduler._exits[0], clip=cfg.clip,
            factored=factored, emit_aux=keep_traces or any_tracer,
        )
        chunk_fn = _build_chunk_fn(key)
        arr = _pack_lanes(lanes, M, P, factored)
        with jax.enable_x64(True):
            L = len(lanes)
            carry = (
                jnp.zeros(L, dtype=jnp.float64),
                jnp.zeros((L, M), dtype=jnp.int32),
                jnp.zeros(L, dtype=jnp.float64),
                jnp.zeros(L, dtype=bool),
                jnp.zeros(L, dtype=bool),
            )
            args = (
                jnp.asarray(arr),
                jnp.asarray(lat_by_cap),
                jnp.asarray(exec_lat),
                jnp.asarray(tau_vec),
                jnp.asarray(limit, dtype=jnp.float64),
            )
            ys_chunks = []
            steps_run = 0
            while True:
                carry, ys = chunk_fn(carry, *args)
                ys_chunks.append(jax.device_get(ys))
                steps_run += S
                done = np.asarray(carry[3])
                overflow = np.asarray(carry[4])
                if bool(done.all()) or bool(overflow.any()):
                    break
                if steps_run >= budget + S:
                    raise RuntimeError(
                        f"scan engine exceeded its step budget "
                        f"({steps_run} rounds for {n_total_max} arrivals); "
                        f"this indicates a termination bug — please report"
                    )
        if bool(np.asarray(carry[4]).any()):
            if Q >= max(n_max, 1):
                raise RuntimeError(
                    "scan engine overflowed a max_queue window already as "
                    "large as the densest arrival trace — please report"
                )
            if any_tracer:
                over = np.asarray(carry[4])
                t_over = np.asarray(carry[0])
                for i, tr in enumerate(tracers):
                    if tr is not None and bool(over[i]):
                        tr.record_event(
                            float(t_over[i]), "overflow-retry",
                            max_queue_from=Q, max_queue_to=Q * 2)
            Q = Q * 2  # retry with a wider window (sticky-flag overflow)
            continue
        break

    names = (
        ("code", "t0", "score", "margin") if key.emit_aux
        else ("code", "t0")
    )
    t_fin = np.asarray(carry[0])
    busy_fin = np.asarray(carry[2])
    cat = {
        n: (
            np.concatenate([np.asarray(c[j]) for c in ys_chunks], axis=1)
            if len(ys_chunks) > 1
            else np.asarray(ys_chunks[0][j])
        )
        for j, n in enumerate(names)
    }
    results = []
    for i, lane in enumerate(lanes):
        lane_ys = {n: col[i] for n, col in cat.items()}
        results.append(_reconstruct(
            lane_ys, lane, table, sched_lat, exec_lat, E, horizon,
            warmup_tasks, model_map, float(busy_fin[i]), float(t_fin[i]),
            keep_completions, keep_traces,
            tracer=tracers[i], slo=cfg.slo,
        ))
    return results


def simulate_scan(
    scheduler: Scheduler,
    table: ProfileTable,
    arrivals: Sequence[Request],
    horizon: float,
    num_models: Optional[int] = None,
    warmup_tasks: int = 100,
    model_map: Optional[Sequence[int]] = None,
    drain_cap: float = 600.0,
    max_queue: Optional[int] = None,
    keep_completions: bool = False,
    keep_traces: bool = False,
    factored: Optional[bool] = None,
    tracer: Optional[Tracer] = None,
) -> SimResult:
    """Compiled twin of ``ServingSimulator(...).run(...)`` for one trace:
    same arguments-to-metrics contract, one ``lax.scan`` instead of the
    Python event loop. See the module docstring for the supported feature
    matrix; unsupported configurations raise :class:`ScanEngineUnsupported`.
    """
    return simulate_scan_batch(
        scheduler, table, [arrivals], horizon,
        num_models=num_models, warmup_tasks=warmup_tasks,
        model_map=model_map, drain_cap=drain_cap, max_queue=max_queue,
        keep_completions=keep_completions, keep_traces=keep_traces,
        factored=factored,
        tracers=None if tracer is None else [tracer],
    )[0]
