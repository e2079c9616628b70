"""Compiled cluster simulator: G per-device schedulers behind one scan.

``repro.core.cluster.ClusterSimulator`` is a pure-Python global event loop:
fine for one fig14 cell, ~20x too slow for thousand-seed confidence bands.
This module compiles the whole cluster run the way ``repro.core.simfast``
compiled the single-device run: fixed-shape array state, one jitted
``lax.scan`` step per *global event* (failure < arrival < device-round at
equal timestamps, then device id — the reference loop's exact ordering),
``jax.vmap`` across independent lanes (seeds x rates).

State layout (per lane):

  * per-(device, model) FIFO queues become ring buffers ``qarr/qew[G, M, Q]``
    with ``qhead/qlen[G, M]`` cursors — unlike the single-device engine the
    queue contents cannot be a window into the sorted arrival array, because
    the dispatcher interleaves arrivals across devices dynamically and
    failover re-pushes orphans out of arrival order;
  * the arrival stream stays one sorted ``[n]`` array; the carry's ``ai``
    cursor is the reference loop's arrival index;
  * device timers: ``pend[G]`` (next scheduling-round time, ``+inf`` = none),
    ``inq[G]`` (a quantum is in flight), ``alive/done[G]``, ``clock/busy[G]``;
  * one int32 round-robin counter (the only dispatcher state that survives
    compilation — see the dispatcher matrix below).

One scan step processes an *arrival burst* plus at most one round: up to
``K`` consecutive arrivals are dispatched first (compiled dispatcher pick
-> ring push -> one-ulp ``nextafter`` poke; each iteration re-checks that
the next event really is an arrival, so a poked wake-up correctly
interrupts the burst), then — if the next event is a device round — the
earliest pending device runs one Algorithm-1 scheduling round (ingest ->
Eq. 5/6 candidate lattice -> Sec. V-C scoring -> Eq. 7 argmin with the
reference tiebreak -> ring pop, quantum occupancy). Folding arrivals into
the round step is pure batching: every per-event computation is identical
to the one-event-per-step layout, but the [candidates x models x queue]
scoring tensor — the dominant per-step cost — is evaluated once per round
instead of once per event, which is what makes thousand-seed cluster
bands affordable at fig14 arrival rates. The per-round math is the
``simfast`` step re-derived for ring-buffer queues and per-device tables;
scoring uses the same factored-exponential fast path / direct
``lattice_stability_scores`` pair, under the same float64 range gate.

Compiled dispatcher family (`SUPPORTED_DISPATCHERS`):

  * ``round-robin`` — cumsum-rank pick over the eligible mask; the counter
    lives in the carry and (like the reference) does *not* advance when a
    single eligible device short-circuits the pick;
  * ``jsq`` — masked integer argmin of queued counts (ties -> lowest id);
  * ``least-loaded`` — masked argmin of the capacity-weighted backlog: the
    in-flight quantum remainder plus a precomputed ``[G, M, Q+1]``
    ``drain_cell`` table folded left-to-right over models, replaying
    ``drain_estimate``'s accumulation order bit-for-bit;
  * ``stability-aware`` — backlog plus the final-exit unit-batch belief
    ``b1_final[G, M]`` (the monotone shortcut the reference documents), but
    only as a *full scan* (``power_d >= fleet size``): the ``k <
    len(eligible)`` branch draws ``numpy.Generator.choice`` samples that
    have no fixed-shape compiled equivalent, so genuine power-of-d
    subsampling is rejected loudly instead of approximated.

Failure/failover runs as host-segmented barriers: the scan freezes every
lane at the next ``fail_at`` time (events strictly before the barrier
execute; the frozen step is a no-op), the host pulls the carry, kills the
device, re-dispatches its orphans in (arrival, req_id) order through a
numpy mirror of the *identical* pick arithmetic (same IEEE ops, same
tiebreaks, shared round-robin counter via the carry), pushes them into the
rings, and resumes the scan at the next barrier. Queue *identity* (which
request sits where) never enters the carry: the host reconstructs it from
the emitted step codes — pushes and pops per (device, model) are both
chronological, so the k-th pop is the k-th push and completions fall out of
pure order bookkeeping, no re-simulation.

Decisions, ``ServingMetrics`` and completions are **bitwise** equal to the
Python ``ClusterSimulator`` on the supported family (property-tested through
``tests/engine_conformance.py``), and a G=1 fleet collapses bitwise to the
single-device ``simulate_scan`` (closing the PR 3 / PR 6 triangle).

Deliberately unsupported (rejected via :class:`ScanEngineUnsupported`,
never approximated): schedulers outside the Algorithm-1 family, non-numpy
scoring backends, per-device drift / online adaptation / service noise,
power-of-d subsampling (above), heterogeneous exit counts, per-request
deadlines varying within a model, and telemetry tracers (the cluster scan
does not reconstruct cluster timelines — use the Python engine to trace;
see docs/simulator.md "Compiled cluster tier").
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

from repro.core.baselines import make_scheduler
from repro.core.cluster import (
    DISPATCHERS,
    ClusterResult,
    DeviceSpec,
    drain_cell,
)
from repro.core.metrics import DeviceMetrics, summarize, summarize_arrays
from repro.core.request import Completion, Request
from repro.core.scheduler import Scheduler, SchedulerConfig
from repro.core.simfast import (
    _FACTORED_RANGE,
    _MAX_QUEUE_DEFAULT,
    _Lane,
    _build_ladder,
    _dense_latency,
    _pow2,
    _unpack_lane,
    _validate_scheduler,
    ScanEngineUnsupported,
)
from repro.core.telemetry import Tracer
from repro.core.urgency import lattice_stability_scores
from repro.core.workloads import TraceColumns

__all__ = [
    "SUPPORTED_DISPATCHERS",
    "simulate_cluster_scan",
    "simulate_cluster_scan_batch",
]

SUPPORTED_DISPATCHERS = ("round-robin", "jsq", "least-loaded",
                         "stability-aware")

# Arrivals absorbed per scan step before the (expensive) scoring round.
# Purely a throughput knob: any value produces identical decisions.
_BURST = 8


# ---------------------------------------------------------------------------
# Compiled chunk
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _ClusterKey:
    """Everything that shapes the compiled cluster step (jit-cache key)."""

    num_devices: int
    num_models: int
    num_exits: int
    max_queue: int        # Q: ring capacity per (device, model)
    pad_len: int          # P: padded arrival-stream length
    chunk_steps: int      # S: lax.scan length per launch
    burst: int            # K: arrivals absorbed per step before the round
    max_batch: int
    ladder: Tuple[Tuple[int, ...], ...]
    allowed: Tuple[bool, ...]
    fallback_exit: int
    clip: float
    factored: bool
    dispatcher: str


@functools.lru_cache(maxsize=32)
def _build_cluster_chunk_fn(key: _ClusterKey):
    """Compile one chunk: every lane advances ``chunk_steps`` global events.
    Returns (carry', (code, t)) with ys stacked step-major."""
    G, M, E, Q = (key.num_devices, key.num_models, key.num_exits,
                  key.max_queue)
    ladder = jnp.asarray(np.array(key.ladder, dtype=np.int32))   # [B+1, R]
    R = int(ladder.shape[1])
    N = M * R
    allowed = jnp.asarray(np.array(key.allowed, dtype=bool))     # [E]
    e0 = key.fallback_exit
    clip = key.clip
    Bmax = key.max_batch
    n_idx = jnp.arange(N)
    cand_queue = jnp.repeat(jnp.arange(M), R)                    # [N]
    pos_q = jnp.arange(Q)[None, :]                               # [1, Q]
    IBIG = jnp.iinfo(jnp.int32).max

    def run_chunk(carry, arr_t, arr_m, arr_ew, lat_by_cap, exec_lat,
                  drain_tab, b1_final, tau_vec, place, limit, barrier):
        # carry (one lane):
        #   ai i32; qarr/qew [G, M, Q] f64; qhead/qlen [G, M] i32;
        #   pend [G] f64 (+inf = no round pending); inq/alive/done [G] bool;
        #   clock/busy [G] f64; rr i32; blocked bool; over bool.
        # arr_t/arr_m/arr_ew: [P] arrival stream (time, model, exp(-a/tau)),
        #   +inf / 0 padded. lat_by_cap: [G, M, B+1, E, R]; exec_lat:
        #   [G, M, E, B+1]; drain_tab: [G, M, Q+1] drain_cell lookup;
        #   b1_final: [G, M] final-exit unit-batch belief; place: [G, M]
        #   placement mask; limit = horizon + drain_cap; barrier = next
        #   failure time (+inf on the last segment).

        def arrival_once(ai, qarr, qew, qhead, qlen, pend, inq, alive,
                         done, rr, over):
            """Process the next event iff it is an unfrozen arrival.

            Exact replay of the reference dispatch: compiled dispatcher
            pick -> ring push -> one-ulp ``nextafter`` poke. Re-derives
            ``is_arr`` from the *current* carry, so an earlier poke in the
            same burst correctly hands control back to the round branch.
            """
            t_arr = arr_t[ai]
            mdl = arr_m[ai]
            t_rnd = jnp.min(pend)
            # kind order at equal time: arrival(1) < device-round(2), so the
            # arrival wins ties; failures(0) are the host barriers, which
            # freeze every event with t >= barrier (events *at* the failure
            # time run after it, exactly the reference's (t, kind) order).
            is_arr = t_arr <= t_rnd
            upd_a = is_arr & (t_arr < barrier) & ~over

            elig = jnp.take(place, mdl, axis=1) & alive          # [G]
            n_elig = jnp.sum(elig.astype(jnp.int32))
            any_elig = n_elig > 0
            single = n_elig == 1
            if key.dispatcher in ("least-loaded", "stability-aware"):
                # effective_backlog: quantum remainder + drain_estimate's
                # left-to-right per-model fold (bitwise — see drain_tab).
                remv = jnp.where(inq, jnp.maximum(pend - t_arr, 0.0), 0.0)
                acc = jnp.zeros((G,), jnp.float64)
                for mm in range(M):
                    acc = acc + jnp.take_along_axis(
                        drain_tab[:, mm, :], qlen[:, mm][:, None], axis=1
                    )[:, 0]
                backlog = remv + acc
            if key.dispatcher == "round-robin":
                rank = jnp.cumsum(elig.astype(jnp.int32))
                want = (rr % jnp.maximum(n_elig, 1)) + 1
                pick_multi = jnp.argmax(elig & (rank == want))
            elif key.dispatcher == "jsq":
                qtot = jnp.sum(qlen, axis=1)
                pick_multi = jnp.argmin(jnp.where(elig, qtot, IBIG))
            elif key.dispatcher == "least-loaded":
                pick_multi = jnp.argmin(jnp.where(elig, backlog, jnp.inf))
            else:  # stability-aware as a full scan (power_d >= G)
                pred = backlog + jnp.take(b1_final, mdl, axis=1)
                pick_multi = jnp.argmin(jnp.where(elig, pred, jnp.inf))
            # one eligible device short-circuits the pick (reference
            # `_dispatch`): no argmin, and no round-robin advance.
            d_pick = jnp.where(single, jnp.argmax(elig),
                               pick_multi).astype(jnp.int32)
            if key.dispatcher == "round-robin":
                rr = jnp.where(upd_a & any_elig & ~single, rr + 1, rr)

            do_push = upd_a & any_elig
            len_dm = qlen[d_pick, mdl]
            over = over | (do_push & (len_dm >= Q))
            slot = (qhead[d_pick, mdl] + len_dm) % Q
            qarr = qarr.at[d_pick, mdl, slot].set(
                jnp.where(do_push, t_arr, qarr[d_pick, mdl, slot]))
            qew = qew.at[d_pick, mdl, slot].set(
                jnp.where(do_push, arr_ew[ai], qew[d_pick, mdl, slot]))
            qlen = qlen.at[d_pick, mdl].add(
                jnp.where(do_push, 1, 0).astype(jnp.int32))
            # poke: one-ulp wake unless a quantum is in flight or the device
            # passed the drain cap (eligibility already implies alive).
            can_poke = do_push & ~done[d_pick] & ~inq[d_pick]
            wake = jnp.nextafter(t_arr, jnp.inf)
            pend = pend.at[d_pick].set(
                jnp.where(can_poke, jnp.minimum(pend[d_pick], wake),
                          pend[d_pick]))
            ai = jnp.where(upd_a, ai + 1, ai)
            code = jnp.where(
                upd_a,
                jnp.where(any_elig, -(d_pick + 1), 0),
                1,
            ).astype(jnp.int32)
            return ai, qarr, qew, qlen, pend, rr, over, code, t_arr

        def step(c, _):
            (ai, qarr, qew, qhead, qlen, pend, inq, alive, done,
             clock, busy, rr, blocked, over) = c

            # ---- arrival burst: up to K dispatches before the round ----
            codes_k, ts_k = [], []
            for _k in range(key.burst):
                (ai, qarr, qew, qlen, pend, rr, over, code_k,
                 t_k) = arrival_once(ai, qarr, qew, qhead, qlen, pend, inq,
                                     alive, done, rr, over)
                codes_k.append(code_k)
                ts_k.append(t_k)

            t_arr = arr_t[ai]
            t_rnd = jnp.min(pend)
            d_rnd = jnp.argmin(pend).astype(jnp.int32)
            is_arr = t_arr <= t_rnd
            t_evt = jnp.where(is_arr, t_arr, t_rnd)
            frozen = ~(t_evt < barrier)
            upd_r = ~frozen & ~over & ~is_arr

            # ---- device round: Algorithm 1 on the ring queues ----
            ending = inq[d_rnd]
            pend = pend.at[d_rnd].set(jnp.where(upd_r, jnp.inf,
                                                pend[d_rnd]))
            inq = inq.at[d_rnd].set(jnp.where(upd_r, False, inq[d_rnd]))
            clock = clock.at[d_rnd].set(
                jnp.where(upd_r, jnp.maximum(clock[d_rnd], t_rnd),
                          clock[d_rnd]))
            skip = done[d_rnd] | (ending & ~alive[d_rnd])
            over_cap = t_rnd > limit
            done = done.at[d_rnd].set(
                jnp.where(upd_r & ~skip & over_cap, True, done[d_rnd]))
            sched_on = upd_r & ~skip & ~over_cap

            ql = qlen[d_rnd]                                     # [M]
            qh = qhead[d_rnd]                                    # [M]
            gather = (qh[:, None] + jnp.arange(Q)[None, :]) % Q  # [M, Q]
            warr = jnp.take_along_axis(qarr[d_rnd], gather, axis=1)
            wew = jnp.take_along_axis(qew[d_rnd], gather, axis=1)
            mask_b = pos_q < ql[:, None]                         # [M, Q]
            # w_max is the FIFO head's wait (QueueSnapshot.w_max): after a
            # failover push the ring is no longer arrival-sorted, and the
            # reference reads the head, not the max.
            w_max = jnp.where(ql > 0, t_rnd - warr[:, 0], 0.0)   # [M]
            cap = jnp.minimum(ql, Bmax)
            batches = ladder[cap]                                # [M, R]
            valid = (batches > 0).reshape(-1)                    # [N]
            lat_sel = jnp.take_along_axis(
                lat_by_cap[d_rnd], cap[:, None, None, None], axis=1
            )[:, 0]                                              # [M, E, R]
            e_ax = jnp.arange(E)[None, :, None]
            feas = (
                (w_max[:, None, None] + lat_sel <= tau_vec[:, None, None])
                & allowed[None, :, None]
            )
            deepest = jnp.max(jnp.where(feas, e_ax, -1), axis=1)  # [M, R]
            e_sel = jnp.where(deepest >= 0, deepest, e0)
            lat_cand = jnp.sum(
                jnp.where(e_sel[:, None, :] == e_ax, lat_sel, 0.0), axis=1
            )                                                    # [M, R]
            cand_batch = batches.reshape(-1)
            cand_lat = lat_cand.reshape(-1)
            if key.factored:
                amp = jnp.exp(
                    (t_rnd + cand_lat[:, None]) / tau_vec[None, :] - 1.0
                )                                                # [N, M]
                urg = jnp.where(
                    mask_b[None, :, :],
                    jnp.minimum(amp[:, :, None] * wew[None, :, :], clip),
                    0.0,
                )
                total = jnp.sum(urg, axis=(1, 2))
                own = urg[n_idx, cand_queue, :]
                removed = jnp.sum(
                    jnp.where(pos_q < cand_batch[:, None], own, 0.0), axis=1
                )
                scores = total - removed
            else:
                w = jnp.where(mask_b, t_rnd - warr, 0.0)
                scores = lattice_stability_scores(
                    w, mask_b.astype(jnp.float64), cand_lat, cand_batch,
                    cand_queue, tau_vec[:, None], clip,
                )
            scores_v = jnp.where(valid, scores, jnp.inf)
            best = jnp.min(scores_v)
            wm_c = jnp.repeat(w_max, R)
            tie = valid & (scores_v == best)
            wm_best = jnp.max(jnp.where(tie, wm_c, -jnp.inf))
            pick = jnp.argmax(tie & (wm_c == wm_best))
            has_work = jnp.any(valid)

            m_star = cand_queue[pick].astype(jnp.int32)
            e_star = e_sel.reshape(-1)[pick].astype(jnp.int32)
            b_star = cand_batch[pick]
            service = exec_lat[d_rnd, m_star, e_star, b_star]
            t_end = t_rnd + service
            is_disp = sched_on & has_work
            qhead = qhead.at[d_rnd, m_star].set(
                jnp.where(is_disp, (qh[m_star] + b_star) % Q,
                          qhead[d_rnd, m_star]))
            qlen = qlen.at[d_rnd, m_star].add(
                jnp.where(is_disp, -b_star, 0))
            busy = busy.at[d_rnd].add(jnp.where(is_disp, service, 0.0))
            pend = pend.at[d_rnd].set(
                jnp.where(is_disp, t_end, pend[d_rnd]))
            inq = inq.at[d_rnd].set(jnp.where(is_disp, True, inq[d_rnd]))
            code_r = jnp.where(
                is_disp,
                2 + d_rnd + G * (m_star + M * (e_star + E * b_star)),
                1,
            ).astype(jnp.int32)

            blocked = blocked | frozen | over
            c2 = (ai, qarr, qew, qhead, qlen, pend, inq, alive, done,
                  clock, busy, rr, blocked, over)
            # ys slots are in execution order: K arrival slots, then the
            # round slot; the host parser consumes the flattened stream.
            code_vec = jnp.stack(
                codes_k + [jnp.where(upd_r, code_r, jnp.int32(1))])
            t_vec = jnp.stack(ts_k + [t_evt])
            return c2, (code_vec, t_vec)

        return lax.scan(step, carry, None, length=key.chunk_steps, unroll=2)

    fn = jax.vmap(
        run_chunk,
        in_axes=((0,) * 14, 0, 0, 0, None, None, None, None, None, None,
                 None, None),
    )
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# Host-side mirror: queue identity, failover, reconstruction
# ---------------------------------------------------------------------------


class _LaneParse:
    """Order bookkeeping for one lane, rebuilt from the emitted step codes.

    ``push[d][m]`` / ``pops[d][m]`` are chronological, and the rings are
    FIFO, so the k-th popped request of a (device, model) pair is its k-th
    pushed one — completions are pure position math, never a re-simulation.
    """

    __slots__ = ("ai", "push", "pops", "stranded", "lost", "dispatched")

    def __init__(self, G: int, M: int):
        self.ai = 0
        self.push: List[List[List[np.ndarray]]] = [
            [[] for _ in range(M)] for _ in range(G)]
        self.pops: List[List[List[Tuple[np.ndarray, ...]]]] = [
            [[] for _ in range(M)] for _ in range(G)]
        self.stranded: List[np.ndarray] = []
        self.lost = 0
        self.dispatched = np.zeros(G, dtype=np.int64)

    def pop_total(self, d: int, m: int) -> int:
        return int(sum(int(p[2].sum()) for p in self.pops[d][m]))

    def queued(self, d: int, m: int) -> np.ndarray:
        """Request indices still queued on (d, m), FIFO order."""
        pushed = (np.concatenate(self.push[d][m])
                  if self.push[d][m] else np.empty(0, np.int64))
        return pushed[self.pop_total(d, m):]


def _parse_chunk(ps: _LaneParse, codes: np.ndarray, ts: np.ndarray,
                 G: int, M: int, E: int, arr_model: np.ndarray) -> None:
    """Fold one chunk's (code, t) stream into the lane mirror (vectorised:
    one boolean-mask pass per touched (device, model) pair)."""
    ev = codes != 1
    if not ev.any():
        return
    codes = codes[ev]
    ts = ts[ev]
    is_a = codes <= 0
    ka = int(is_a.sum())
    # arrival events appear in global arrival order: the j-th one of this
    # chunk is request ps.ai + j.
    if ka:
        acodes = codes[is_a]
        gi = ps.ai + np.arange(ka, dtype=np.int64)
        routed = acodes <= -1
        devs = (-(acodes + 1)).astype(np.int64)
        mods = arr_model[gi]
        if routed.any():
            ps.dispatched += np.bincount(devs[routed], minlength=G)
            pair = devs[routed] * M + mods[routed]
            gir = gi[routed]
            for p in np.unique(pair):
                d, m = divmod(int(p), M)
                ps.push[d][m].append(gir[pair == p])
        if (~routed).any():
            ps.stranded.append(gi[~routed])
            ps.lost += int((~routed).sum())
        ps.ai += ka
    rnd = codes >= 2
    if rnd.any():
        v = (codes[rnd] - 2).astype(np.int64)
        d = v % G
        u = v // G
        m = u % M
        e = (u // M) % E
        b = u // (M * E)
        t = ts[rnd]
        pair = d * M + m
        for p in np.unique(pair):
            dd, mm = divmod(int(p), M)
            sel = pair == p
            ps.pops[dd][mm].append((t[sel], e[sel], b[sel]))


def _host_backlog(d: int, t: float, pend: np.ndarray, inq: np.ndarray,
                  qlen: np.ndarray, drain_tab: np.ndarray, M: int) -> float:
    """numpy mirror of the compiled effective_backlog (same IEEE op order)."""
    rem = (max(float(pend[d]) - t, 0.0) if bool(inq[d]) else 0.0)
    acc = 0.0
    for mm in range(M):
        acc = acc + float(drain_tab[d, mm, int(qlen[d, mm])])
    return rem + acc


def _host_fail(ps: _LaneParse, st: dict, d_fail: int, t: float,
               lane: _Lane, ew_lane: np.ndarray, reqid: np.ndarray,
               placement: Sequence[Sequence[int]], dispatcher: str,
               drain_tab: np.ndarray, b1_final: np.ndarray, Q: int,
               M: int) -> bool:
    """Kill ``d_fail`` at barrier time ``t`` and failover its queue through
    the same pick arithmetic the compiled step runs. Mutates the numpy carry
    views in ``st`` and the lane mirror. Returns True on ring overflow
    (caller retries the whole run with a wider ring)."""
    alive, done, inq, pend = st["alive"], st["done"], st["inq"], st["pend"]
    qarr, qew, qhead, qlen = st["qarr"], st["qew"], st["qhead"], st["qlen"]
    alive[d_fail] = False
    if not bool(inq[d_fail]):
        pend[d_fail] = np.inf
    orphans = []
    for m in range(M):
        idxs = ps.queued(d_fail, m)
        if len(idxs):
            orphans.append(idxs)
        # truncate the mirror to the consumed prefix; the ring empties
        consumed = ps.pop_total(d_fail, m)
        pushed = (np.concatenate(ps.push[d_fail][m])
                  if ps.push[d_fail][m] else np.empty(0, np.int64))
        ps.push[d_fail][m] = [pushed[:consumed]] if consumed else []
        qlen[d_fail, m] = 0
    if not orphans:
        return False
    orph = np.concatenate(orphans)
    order = np.lexsort((reqid[orph], lane.arrival[orph]))
    orph = orph[order]
    wake = np.nextafter(t, np.inf)
    for ridx in orph:
        ridx = int(ridx)
        m = int(lane.model[ridx])
        elig = [dd for dd in placement[m] if bool(alive[dd])]
        if not elig:
            ps.stranded.append(np.array([ridx], dtype=np.int64))
            ps.lost += 1
            continue
        if len(elig) == 1:
            pick = elig[0]
        elif dispatcher == "round-robin":
            pick = elig[st["rr"] % len(elig)]
            st["rr"] += 1
        elif dispatcher == "jsq":
            pick = min(elig, key=lambda dd: (int(qlen[dd].sum()), dd))
        elif dispatcher == "least-loaded":
            pick = min(elig, key=lambda dd: (
                _host_backlog(dd, t, pend, inq, qlen, drain_tab, M), dd))
        else:  # stability-aware full scan
            pick = min(elig, key=lambda dd: (
                _host_backlog(dd, t, pend, inq, qlen, drain_tab, M)
                + float(b1_final[dd, m]), dd))
        if int(qlen[pick, m]) >= Q:
            return True  # ring overflow: retry wider
        slot = (int(qhead[pick, m]) + int(qlen[pick, m])) % Q
        qarr[pick, m, slot] = lane.arrival[ridx]
        qew[pick, m, slot] = ew_lane[ridx]
        qlen[pick, m] += 1
        ps.push[pick][m].append(np.array([ridx], dtype=np.int64))
        ps.dispatched[pick] += 1
        if not bool(done[pick]) and not bool(inq[pick]):
            pend[pick] = min(float(pend[pick]), wake)
    return False


def _rollup(lane: _Lane, ps: _LaneParse, specs: Sequence[DeviceSpec],
            cfg: SchedulerConfig, exec_lat: np.ndarray, reqid: np.ndarray,
            clock_row: np.ndarray, busy_row: np.ndarray,
            qlen_row: np.ndarray, alive_row: np.ndarray, horizon: float,
            warmup_tasks: int, keep_completions: bool) -> ClusterResult:
    """Reference-identical rollup: merged (finish, req_id) completion order,
    shared-span utilisation, per-device summarize() slices."""
    G = len(specs)
    M = len(lane.tau_vec)
    cols_m, cols_e, cols_b, cols_ri, cols_t0, cols_t1, cols_own = (
        [], [], [], [], [], [], [])
    for d in range(G):
        for m in range(M):
            plist = ps.pops[d][m]
            if not plist:
                continue
            t = np.concatenate([p[0] for p in plist])
            e = np.concatenate([p[1] for p in plist])
            b = np.concatenate([p[2] for p in plist])
            total = int(b.sum())
            pushed = (np.concatenate(ps.push[d][m])
                      if ps.push[d][m] else np.empty(0, np.int64))
            ridx = pushed[:total]
            # finish = t + L(d, m, e, B): the identical IEEE add the scan
            # performed when it occupied the quantum.
            fin = t + exec_lat[d, m, e, b]
            cols_m.append(np.full(total, m, dtype=np.int64))
            cols_e.append(np.repeat(e, b))
            cols_b.append(np.repeat(b, b))
            cols_ri.append(ridx)
            cols_t0.append(np.repeat(t, b))
            cols_t1.append(np.repeat(fin, b))
            cols_own.append(np.full(total, d, dtype=np.int64))
    if cols_m:
        model = np.concatenate(cols_m)
        exits = np.concatenate(cols_e)
        batch = np.concatenate(cols_b)
        ridx = np.concatenate(cols_ri)
        disp = np.concatenate(cols_t0)
        fin = np.concatenate(cols_t1)
        own = np.concatenate(cols_own)
        rid = reqid[ridx]
        order = np.lexsort((rid, fin))
        model, exits, batch = model[order], exits[order], batch[order]
        ridx, disp, fin = ridx[order], disp[order], fin[order]
        own, rid = own[order], rid[order]
    else:
        model = exits = batch = ridx = own = rid = np.empty(0, np.int64)
        disp = fin = np.empty(0, np.float64)

    span = max(max(float(c) for c in clock_row), horizon)
    residual = int(qlen_row.sum()) + ps.lost
    busy = sum(float(x) for x in busy_row)
    arrival = lane.arrival[ridx]

    if keep_completions:
        comps = [
            Completion(
                req_id=int(rid[i]), model=int(model[i]),
                arrival=float(arrival[i]), dispatch=float(disp[i]),
                finish=float(fin[i]), exit_idx=int(exits[i]),
                batch_size=int(batch[i]),
                deadline=lane.requests[int(ridx[i])].deadline,
            )
            for i in range(len(model))
        ]
        metrics = summarize(
            comps, specs[0].table, cfg.slo, warmup_tasks=warmup_tasks,
            busy_time=busy, span=span, residual_queue=residual, dropped=0,
        )
    else:
        comps = []
        metrics = summarize_arrays(
            models=model, exits=exits, batches=batch,
            latencies=fin - arrival, queueings=disp - arrival,
            taus=lane.tau_vec[model] if len(model) else np.empty(0),
            table=specs[0].table, warmup_tasks=warmup_tasks,
            busy_time=busy, span=span, residual_queue=residual, dropped=0,
        )

    wu = metrics.warmup_used
    own_done = own[wu:]
    per_dev = []
    for d in range(G):
        sel = own_done == d
        nd = int(sel.sum())
        if keep_completions:
            mine = [c for c, keep in zip(comps[wu:], sel) if keep]
            dm = summarize(mine, specs[d].table, cfg.slo, warmup_tasks=0,
                           dropped=0)
        else:
            dm = summarize_arrays(
                models=model[wu:][sel], exits=exits[wu:][sel],
                batches=batch[wu:][sel],
                latencies=(fin - arrival)[wu:][sel],
                queueings=(disp - arrival)[wu:][sel],
                taus=lane.tau_vec[model[wu:][sel]] if nd else np.empty(0),
                table=specs[d].table, warmup_tasks=0, dropped=0,
            )
        per_dev.append(DeviceMetrics(
            device=d, name=specs[d].label(d), num_completed=nd,
            dispatched=int(ps.dispatched[d]), dropped=0,
            violation_ratio=dm.violation_ratio, p95_latency=dm.p95_latency,
            mean_exit_depth=dm.mean_exit_depth,
            utilization=float(float(busy_row[d]) / span) if span > 0
            else 0.0,
            alive=bool(alive_row[d]),
        ))
    metrics = dataclasses.replace(
        metrics,
        utilization=(busy / (span * G)) if span > 0 else 0.0,
        per_device=tuple(per_dev),
    )
    return ClusterResult(metrics=metrics, completions=comps, span=span,
                         trace=None)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------


def _validate_cluster(specs: Sequence[DeviceSpec], dispatcher: str,
                      power_d: int, tracer, scheds: Sequence[Scheduler],
                      noise_cov: float) -> None:
    G = len(specs)
    if dispatcher not in DISPATCHERS:
        raise ValueError(
            f"unknown dispatcher {dispatcher!r}; "
            f"available: {sorted(DISPATCHERS)}"
        )
    if dispatcher == "stability-aware" and power_d < G:
        raise ScanEngineUnsupported(
            f"stability-aware power-of-d subsampling (power_d={power_d} < "
            f"fleet size {G}) draws numpy Generator.choice samples with no "
            f"fixed-shape compiled equivalent; the scan engine supports "
            f"stability-aware only as a full scan (power_d >= fleet size) "
            f"— use the Python ClusterSimulator for true power-of-d"
        )
    if tracer is not None:
        raise ScanEngineUnsupported(
            "the cluster scan engine does not reconstruct telemetry "
            "timelines (documented loud-reject; see docs/simulator.md) — "
            "trace cluster runs with the Python ClusterSimulator"
        )
    if noise_cov > 0:
        raise ScanEngineUnsupported(
            "service-time noise draws per-quantum RNG the compiled step "
            "does not reproduce; use the Python engine"
        )
    E = specs[0].table.num_exits
    for d, spec in enumerate(specs):
        if spec.drift is not None:
            raise ScanEngineUnsupported(
                f"device {d} carries a DriftModel; per-device drift needs "
                f"the Python ClusterSimulator"
            )
        if spec.table.num_exits != E:
            raise ScanEngineUnsupported(
                f"device {d} has {spec.table.num_exits} exits but device 0 "
                f"has {E}; the compiled lattice is one fixed [E] axis"
            )
    for sched in scheds:
        _validate_scheduler(sched)


def simulate_cluster_scan_batch(
    devices: Sequence[DeviceSpec],
    arrival_lanes: Sequence[Sequence[Request]],
    horizon: float,
    policy: str = "edgeserving",
    config: Optional[SchedulerConfig] = None,
    dispatcher: str = "least-loaded",
    power_d: int = 2,
    num_models: Optional[int] = None,
    warmup_tasks: int = 100,
    seed: int = 0,
    drain_cap: float = 600.0,
    max_queue: Optional[int] = None,
    keep_completions: bool = True,
    factored: Optional[bool] = None,
    service_noise_cov: float = 0.0,
    tracer: Optional[Tracer] = None,
) -> List[ClusterResult]:
    """Run one cluster experiment per arrival lane, all lanes side by side
    in one jitted, vmapped ``lax.scan`` — the compiled twin of
    ``ClusterSimulator(devices, ...).run(lane, horizon)`` (``seed`` is
    accepted for signature parity; the supported family draws no RNG).
    Returns one :class:`ClusterResult` per lane, in order. Unsupported
    features raise :class:`ScanEngineUnsupported`; see the module docstring
    for the dispatcher matrix and the failover protocol.

    ``keep_completions=False`` skips building per-request ``Completion``
    objects and computes the identical metrics through ``summarize_arrays``
    (the proven-equal array twin) — the seed-band path uses this to stay
    vectorised at 10^3 lanes.
    """
    specs = list(devices)
    G = len(specs)
    assert G >= 1
    cfg = config or SchedulerConfig()
    M = num_models or specs[0].table.num_models
    scheds = [make_scheduler(policy, s.table, cfg) for s in specs]
    _validate_cluster(specs, dispatcher, power_d, tracer, scheds,
                      service_noise_cov)
    placement = [
        [d for d, s in enumerate(specs)
         if s.models is None or m in s.models]
        for m in range(M)
    ]
    for m, hosts in enumerate(placement):
        assert hosts, f"model {m} is placed on no device"

    lanes = [_unpack_lane(lane, M, cfg.slo) for lane in arrival_lanes]
    if not lanes:
        return []
    tau_vec = lanes[0].tau_vec
    for lane in lanes[1:]:
        if not np.array_equal(lane.tau_vec, tau_vec):
            raise ScanEngineUnsupported(
                "all lanes of one cluster scan batch must share the same "
                "per-model deadline vector (split differing lanes into "
                "separate calls)"
            )

    E = specs[0].table.num_exits
    Bmax = cfg.max_batch
    ladder = _build_ladder(scheds[0], Bmax)
    allowed = tuple(e in scheds[0]._exits for e in range(E))
    # Per-device tables: scheduler belief == execution ground truth in the
    # cluster tier (no sched_table / model_map deployment mixing here).
    dense = np.stack([
        _dense_latency(s.table, list(range(M)), E, Bmax) for s in specs
    ])                                                   # [G, M, E, B+1]
    exec_lat = dense
    ladder_np = np.array(ladder, dtype=np.int64)
    lat_by_cap = np.ascontiguousarray(np.stack([
        dense[d][:, :, ladder_np].transpose(0, 2, 1, 3) for d in range(G)
    ]))                                                  # [G, M, B+1, E, R]
    b1_final = np.array(
        [[s.table(m, E - 1, 1) for m in range(M)] for s in specs],
        dtype=np.float64,
    )
    place_np = np.zeros((G, M), dtype=bool)
    for m, hosts in enumerate(placement):
        for d in hosts:
            place_np[d, m] = True

    n_total_max = max((len(lane.model) for lane in lanes), default=0)
    n_qmax = max(
        (max((len(ix) for ix in lane.by_model), default=0)
         for lane in lanes),
        default=0,
    )
    last_arrival = max(
        (lane.arrival[-1] for lane in lanes if len(lane.arrival)),
        default=0.0,
    )
    if factored is None:
        factored = bool(last_arrival / tau_vec.min() <= _FACTORED_RANGE)

    reqids = [
        np.arange(len(lane.requests), dtype=np.int64)
        if isinstance(lane.requests, TraceColumns)   # req_id == row index
        else np.fromiter(map(operator.attrgetter("req_id"), lane.requests),
                         dtype=np.int64, count=len(lane.requests))
        for lane in lanes
    ]
    fails = sorted(
        (float(s.fail_at), d) for d, s in enumerate(specs)
        if s.fail_at is not None
    )
    barrier_groups: List[Tuple[float, List[int]]] = []
    for tf, d in fails:
        if barrier_groups and barrier_groups[-1][0] == tf:
            barrier_groups[-1][1].append(d)
        else:
            barrier_groups.append((tf, [d]))
    segments = barrier_groups + [(np.inf, [])]
    F = len(fails)
    limit = horizon + drain_cap
    L = len(lanes)
    P = _pow2(n_total_max + 1)
    budget = (4 + 3 * F) * max(n_total_max, 1) + 4 * G + 64
    S = min(_pow2(budget), 256)

    arr_t = np.full((L, P), np.inf, dtype=np.float64)
    arr_m = np.zeros((L, P), dtype=np.int32)
    arr_ew = np.zeros((L, P), dtype=np.float64)
    for li, lane in enumerate(lanes):
        n = len(lane.model)
        arr_t[li, :n] = lane.arrival
        arr_m[li, :n] = lane.model
        if factored:
            arr_ew[li, :n] = np.exp(-lane.arrival / tau_vec[lane.model])

    Q = max_queue or min(_MAX_QUEUE_DEFAULT, _pow2(max(n_qmax, 1)))
    while True:
        key = _ClusterKey(
            num_devices=G, num_models=M, num_exits=E, max_queue=Q,
            pad_len=P, chunk_steps=S, burst=_BURST, max_batch=Bmax,
            ladder=ladder,
            allowed=allowed, fallback_exit=scheds[0]._exits[0],
            clip=cfg.clip, factored=factored, dispatcher=dispatcher,
        )
        chunk_fn = _build_cluster_chunk_fn(key)
        drain_tab = np.zeros((G, M, Q + 1), dtype=np.float64)
        for d, s in enumerate(scheds):
            for m in range(M):
                for q in range(1, Q + 1):
                    drain_tab[d, m, q] = drain_cell(s, m, q)
        parse = [_LaneParse(G, M) for _ in lanes]
        overflowed = False
        with jax.enable_x64(True):
            shared = (
                jnp.asarray(lat_by_cap), jnp.asarray(exec_lat),
                jnp.asarray(drain_tab), jnp.asarray(b1_final),
                jnp.asarray(tau_vec), jnp.asarray(place_np),
                jnp.asarray(limit, dtype=jnp.float64),
            )
            carry_np = {
                "ai": np.zeros(L, np.int32),
                "qarr": np.zeros((L, G, M, Q), np.float64),
                "qew": np.zeros((L, G, M, Q), np.float64),
                "qhead": np.zeros((L, G, M), np.int32),
                "qlen": np.zeros((L, G, M), np.int32),
                "pend": np.full((L, G), np.inf, np.float64),
                "inq": np.zeros((L, G), bool),
                "alive": np.ones((L, G), bool),
                "done": np.zeros((L, G), bool),
                "clock": np.zeros((L, G), np.float64),
                "busy": np.zeros((L, G), np.float64),
                "rr": np.zeros(L, np.int32),
                "blocked": np.zeros(L, bool),
                "over": np.zeros(L, bool),
            }
            names = ("ai", "qarr", "qew", "qhead", "qlen", "pend", "inq",
                     "alive", "done", "clock", "busy", "rr", "blocked",
                     "over")
            carry = tuple(jnp.asarray(carry_np[n]) for n in names)
            steps_run = 0
            step_cap = budget + (len(segments) + 2) * S
            for bt, dying in segments:
                # fresh segment: clear the barrier-freeze flags
                blocked0 = jnp.zeros(L, bool)
                carry = carry[:12] + (blocked0, carry[13])
                barrier_j = jnp.asarray(bt, dtype=jnp.float64)
                while True:
                    carry, ys = chunk_fn(
                        carry, jnp.asarray(arr_t), jnp.asarray(arr_m),
                        jnp.asarray(arr_ew), *shared, barrier_j)
                    steps_run += S
                    codes, tvals = jax.device_get(ys)
                    for li in range(L):
                        # [S, K+1] slots flatten to the execution-order
                        # event stream the mirror expects
                        _parse_chunk(parse[li],
                                     np.asarray(codes[li]).reshape(-1),
                                     np.asarray(tvals[li]).reshape(-1),
                                     G, M, E, arr_m[li])
                    blocked = np.asarray(carry[12])
                    over = np.asarray(carry[13])
                    if bool(over.any()):
                        overflowed = True
                        break
                    if bool(blocked.all()):
                        break
                    if steps_run > step_cap:
                        raise RuntimeError(
                            f"cluster scan exceeded its step budget "
                            f"({steps_run} events for {n_total_max} "
                            f"arrivals, {F} failures); this indicates a "
                            f"termination bug — please report"
                        )
                if overflowed:
                    break
                if not dying:
                    continue
                host = [np.array(jax.device_get(c)) for c in carry]
                st_all = dict(zip(names, host))
                for li in range(L):
                    st = {k: st_all[k][li] for k in names}
                    # the round-robin counter continues from the compiled
                    # picks; host picks advance it and hand it back
                    st["rr"] = int(st_all["rr"][li])
                    for d_fail in dying:
                        if _host_fail(
                            parse[li], st, d_fail, bt, lanes[li],
                            arr_ew[li], reqids[li], placement, dispatcher,
                            drain_tab, b1_final, Q, M,
                        ):
                            overflowed = True
                            break
                    st_all["rr"][li] = st["rr"]
                    if overflowed:
                        break
                if overflowed:
                    break
                carry = tuple(jnp.asarray(st_all[n]) for n in names)
        if overflowed:
            if Q >= max(n_qmax, 1):
                raise RuntimeError(
                    "cluster scan overflowed a ring already as large as "
                    "the densest per-model arrival count — please report"
                )
            Q *= 2  # retry with a wider ring (sticky-flag overflow)
            continue
        break

    final = [np.asarray(jax.device_get(c)) for c in carry]
    fin = dict(zip(names, final))
    results = []
    for li, lane in enumerate(lanes):
        assert parse[li].ai == len(lane.model), "arrival stream not drained"
        results.append(_rollup(
            lane, parse[li], specs, cfg, exec_lat, reqids[li],
            fin["clock"][li], fin["busy"][li], fin["qlen"][li],
            fin["alive"][li], horizon, warmup_tasks, keep_completions,
        ))
    return results


def simulate_cluster_scan(
    devices: Sequence[DeviceSpec],
    arrivals: Sequence[Request],
    horizon: float,
    **kwargs,
) -> ClusterResult:
    """Compiled twin of ``ClusterSimulator(devices, ...).run(arrivals,
    horizon)`` for one trace: same arguments-to-metrics contract, one
    ``lax.scan`` instead of the Python global event loop. See
    :func:`simulate_cluster_scan_batch` for the supported feature matrix."""
    return simulate_cluster_scan_batch(
        devices, [arrivals], horizon, **kwargs)[0]
