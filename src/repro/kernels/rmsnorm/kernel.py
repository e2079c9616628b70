"""RMSNorm as a Pallas TPU kernel: row-tiled, feature-resident.

grid = (T/bt,); block [bt, D] with the full feature dim resident so the
mean-square reduction is a single VMEM pass; fp32 accumulation, output in
the input dtype. Input and output blocks are double-buffered, so fp32
needs 16 * bt * D bytes of VMEM: 8 MiB at bt=128, D=4096, inside v5e's
16 MiB scoped limit (bt=256 exceeds it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, g_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) * g[None, :]).astype(
        o_ref.dtype)


def rmsnorm_kernel(x, gain, *, eps: float = 1e-6, block_t: int = 128,
                   interpret: bool = False):
    """x [T, D]; gain [D] -> [T, D]."""
    t, d = x.shape
    bt = min(block_t, t)
    assert t % bt == 0, (t, bt)
    return pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(t // bt,),
        in_specs=[
            pl.BlockSpec((bt, d), lambda it: (it, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((d,), lambda it: (0,),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((bt, d), lambda it: (it, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((t, d), x.dtype),
        interpret=interpret,
    )(x, gain)
