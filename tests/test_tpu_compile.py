"""The main-path Pallas kernels compile for a TPU v5e.

Each test compiles one kernel at its serving shape against a *described*
v5e chip (no chip attached): the TPU compiler runs here and raises what it
would raise on the chip, which interpret mode cannot show. Nothing runs,
so these tests say nothing about results or speed.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and under several test workers
only the worker given this file may do so. Keep every such compile in this
one file.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.exit_head.ops import exit_head
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.stability_score.ops import stability_scores


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def compile_for_chip(one_chip, no_persistent_cache):
    def compile_(fn, *shapes):
        specs = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                 for s, dt in shapes]
        compiled = jax.jit(fn).lower(*specs).compile()
        assert "tpu_custom_call" in compiled.as_text()
        return compiled

    return compile_


F32, I32 = jnp.float32, jnp.int32


@pytest.mark.parametrize("m,q,n,layout", [
    (3, 32, 3, "greedy"),       # the paper's M = 3, one candidate per queue
    (3, 32, 12, "lattice"),     # M = 3, 4 exits x 3 batch rungs
    (64, 64, 64, "lattice"),    # many tenants
    (64, 64, 512, "lattice"),
])
def test_stability_score(compile_for_chip, m, q, n, layout):
    queues = [((m, q), F32), ((m, q), F32), ((n,), F32), ((n,), I32)]
    if layout == "greedy":
        compile_for_chip(
            lambda w, mask, lat, bat, tau: stability_scores(
                w, mask, lat, bat, tau=tau),
            *queues, ((), F32))
    else:
        compile_for_chip(
            lambda w, mask, lat, bat, cq, tau: stability_scores(
                w, mask, lat, bat, cq, tau=tau),
            *queues, ((n,), I32), ((m, q), F32))


def test_exit_head(compile_for_chip):
    t, d, v = 256, 576, 49152            # benchmarks/micro_kernels.py
    compile_for_chip(lambda h, g, w: exit_head(h, g, w),
                     ((t, d), F32), ((d,), F32), ((d, v), F32))


def test_rmsnorm(compile_for_chip):
    compile_for_chip(lambda x, g: rmsnorm(x, g),
                     ((4096, 4096), F32), ((4096,), F32))


def test_flash_attention(compile_for_chip):
    b, h, kh, s, d = 1, 8, 2, 1024, 64
    compile_for_chip(lambda q, k, v: flash_attention(q, k, v, causal=True),
                     ((b, h, s, d), F32), ((b, kh, s, d), F32),
                     ((b, kh, s, d), F32))
