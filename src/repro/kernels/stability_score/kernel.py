"""Candidate stability scoring (paper Eq. 3-7) as a Pallas TPU kernel.

The scheduler evaluates N candidate decisions per round; each candidate n
rescores *every* queued task under the predicted wait shift L_n — an
O(N * M * maxQ) fused pass. Candidates are a flattened (model, exit, batch)
lattice: ``cand_queue[n]`` names the queue candidate n would serve, so the
paper's one-candidate-per-queue greedy (N == M, cand_queue == arange) and
the joint lattice (N == sum over queues of |ladder| * |exits|) share one
kernel.

Deadlines: ``tau`` is an ``[M, Q]`` per-task deadline matrix held in VMEM
alongside the wait matrix (heterogeneous-SLO workloads); scalar-SLO callers
pass the filled matrix the wrapper builds for them — bitwise-identical to
dividing by the scalar. ``clip`` rides along as a (1, 1) traced SMEM scalar
so an SLO/clip sweep never recompiles (see ops.py).

Layout (what Mosaic accepts on v5e): every operand is rank 2. The queue
matrices are padded to whole (8, 128) tiles and sit in VMEM in full;
candidates lie on the sublane axis as ``(bn, 1)`` column blocks with
``bn`` a multiple of 8, so grid = (Npad / bn,). The kernel walks the M
queue rows with a ``fori_loop``: row m is a ``(1, Q)`` lane vector,
broadcast against the ``(bn, 1)`` candidate latencies into one ``(bn, Q)``
tile, which is reduced over lanes. No rank-3 intermediate is formed.
Padded rows and lanes carry ``mask == 0`` and ``tau == 1``, so they add
nothing; padded candidates are sliced off by the wrapper.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SUBLANES, _LANES = 8, 128


def _round_up(x: int, k: int) -> int:
    return -(-x // k) * k


def _score_kernel(w_ref, mask_ref, tau_ref, clip_ref, lat_ref, batch_ref,
                  queue_ref, out_ref):
    clip = clip_ref[0, 0]                               # traced scalar
    log_clip = jnp.log(clip)
    lat = lat_ref[...]                                  # [bn, 1] f32
    batch = batch_ref[...]                              # [bn, 1] i32
    queue = queue_ref[...]                              # [bn, 1] i32
    bn = lat.shape[0]
    q = w_ref.shape[1]
    pos = jax.lax.broadcasted_iota(jnp.int32, (bn, q), 1)
    served_pos = pos < batch                            # [bn, Q]

    def row(m, acc):
        total, removed = acc
        w = w_ref[pl.ds(m, 1), :]                       # [1, Q]
        tau = tau_ref[pl.ds(m, 1), :]
        mask = mask_ref[pl.ds(m, 1), :]
        urg = jnp.minimum(
            jnp.exp(jnp.minimum((w + lat) / tau - 1.0, log_clip)), clip
        ) * mask                                        # [bn, Q]
        total = total + jnp.sum(urg, axis=1, keepdims=True)
        # served tasks (B oldest of the candidate's target queue) are removed
        served = served_pos & (queue == m)
        removed = removed + jnp.sum(jnp.where(served, urg, 0.0), axis=1,
                                    keepdims=True)
        return total, removed

    zero = jnp.zeros((bn, 1), jnp.float32)
    total, removed = jax.lax.fori_loop(0, w_ref.shape[0], row, (zero, zero))
    out_ref[...] = total - removed


def stability_scores_kernel(w, mask, cand_latency, cand_batch,
                            cand_queue=None, *, tau, clip=10.0,
                            block_m: int = 8, interpret: bool = False):
    """w, mask [M, Q]; cand_latency [N] f32; cand_batch, cand_queue [N] i32
    -> [N] f32. ``cand_queue=None`` means the one-candidate-per-queue greedy
    layout (N == M, candidate n serves queue n). ``tau`` is a scalar SLO or
    an [M, Q] per-task deadline matrix; ``clip`` a (traced) scalar.
    ``block_m`` is the number of candidates per grid step, rounded up to a
    whole sublane tile."""
    m, q = w.shape
    if cand_queue is None:
        cand_queue = jnp.arange(m, dtype=jnp.int32)
    # scalar tau -> filled matrix (bitwise-identical to scalar division);
    # matrix tau is forwarded as-is.
    tau = jnp.broadcast_to(jnp.asarray(tau, jnp.float32), (m, q))
    clip = jnp.asarray(clip, jnp.float32).reshape(1, 1)
    mp, qp = _round_up(m, _SUBLANES), _round_up(q, _LANES)
    pad_mq = ((0, mp - m), (0, qp - q))
    w = jnp.pad(w.astype(jnp.float32), pad_mq)
    mask = jnp.pad(mask.astype(jnp.float32), pad_mq)
    tau = jnp.pad(tau, pad_mq, constant_values=1.0)

    n = cand_latency.shape[0]
    bn = min(_round_up(block_m, _SUBLANES), _round_up(n, _SUBLANES))
    np_ = _round_up(n, bn)

    def column(x):
        # padded candidates score garbage; sliced off below
        return jnp.pad(x, (0, np_ - n)).reshape(np_, 1)

    def whole(shape):
        return pl.BlockSpec(shape, lambda ic: (0, 0),
                            memory_space=pltpu.VMEM)

    def cand_block():
        return pl.BlockSpec((bn, 1), lambda ic: (ic, 0),
                            memory_space=pltpu.VMEM)

    out = pl.pallas_call(
        _score_kernel,
        grid=(np_ // bn,),
        in_specs=[
            whole((mp, qp)),
            whole((mp, qp)),
            whole((mp, qp)),
            # traced clip scalar: control-flow-style operand, SMEM-resident
            pl.BlockSpec((1, 1), lambda ic: (0, 0),
                         memory_space=pltpu.SMEM),
            cand_block(),
            cand_block(),
            cand_block(),
        ],
        out_specs=cand_block(),
        out_shape=jax.ShapeDtypeStruct((np_, 1), jnp.float32),
        interpret=interpret,
    )(w, mask, tau, clip, column(cand_latency), column(cand_batch),
      column(cand_queue))
    return out[:n, 0]
