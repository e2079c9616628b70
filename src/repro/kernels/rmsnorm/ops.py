"""Public wrapper for the RMSNorm kernel."""

from __future__ import annotations

import functools

import jax

from repro.kernels.rmsnorm.kernel import rmsnorm_kernel
from repro.kernels.rmsnorm.ref import rmsnorm_ref


@functools.partial(jax.jit, static_argnames=("eps", "block_t", "interpret",
                                             "use_kernel"))
def rmsnorm(x, gain, *, eps: float = 1e-6, block_t: int = 128,
            interpret: bool = False, use_kernel: bool = True):
    """RMSNorm over the last dim of a 2D input."""
    if not use_kernel:
        return rmsnorm_ref(x, gain, eps)
    return rmsnorm_kernel(x, gain, eps=eps, block_t=block_t,
                          interpret=interpret)
