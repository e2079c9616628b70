"""``ServedModel`` hands its executables the weights packed: the vectors
of one (shape, dtype) stacked into one buffer, each matrix a buffer of its
own. The packed call must serve what the per-leaf call served, pack once,
and count the buffers it hands over."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ProfileTable, Request, SchedulerConfig, make_scheduler
from repro.models.common import split_params
from repro.models.resnet import EarlyExitResNet, ResNetConfig
from repro.runtime import server
from repro.runtime.server import ServedModel, ServingEngine, pack_values

BATCHES = (1, 3)


@pytest.fixture(scope="module")
def resnet():
    net = EarlyExitResNet(ResNetConfig(width_multiplier=0.125,
                                       blocks_override=(1, 2, 1, 1)))
    values, _ = split_params(net.init(jax.random.key(0)))
    x = jax.random.normal(jax.random.key(1), (max(BATCHES), 32, 32, 3))
    model = ServedModel("r", values, net.forward_exit, lambda b: x[:b], 4)
    return net, values, x, model


def _buffers(values):
    """Vector groups plus matrices: what ``pack_values`` should make."""
    leaves = jax.tree_util.tree_leaves(values)
    vectors = {(np.shape(v), np.result_type(v)) for v in leaves
               if np.ndim(v) <= 1}
    return len(vectors) + sum(np.ndim(v) > 1 for v in leaves)


@pytest.mark.parametrize("e", range(4))
@pytest.mark.parametrize("b", BATCHES)
def test_packed_execute_serves_the_unpacked_logits(resnet, e, b):
    net, values, x, model = resnet
    got = np.asarray(model.execute(e, b))
    want = np.asarray(jax.jit(
        lambda v, x: net.forward_exit(v, x, e)).lower(values, x[:b])
        .compile()(values, x[:b]))
    assert got.shape == (b, 100)
    # float32: the compiler may fuse the sliced weights differently
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * np.abs(want).max())


def test_buffers_are_vector_groups_matrices_and_the_input(resnet):
    _, values, _, model = resnet
    model.execute(3, 1)
    n_leaves = len(jax.tree_util.tree_leaves(values))
    n_buffers = _buffers(values)
    assert n_buffers < n_leaves
    assert len(model.packed) == n_buffers
    assert model.launch_buffers == n_buffers + 1
    assert model.executables[(3, 1)].in_tree.num_leaves == n_buffers + 1


def test_unpack_rebuilds_every_leaf():
    values = {"w": [jnp.full((2, 3), i, jnp.float32) for i in range(4)],
              "s": jnp.arange(3, dtype=jnp.int32),
              "b": [jnp.float32(i) for i in range(3)]}
    packed, unpack = pack_values(values)
    assert [p.shape for p in packed] == [(3,), (1, 3)] + [(2, 3)] * 4
    assert all(p is w for p, w in zip(packed[2:], values["w"]))
    back = jax.jit(unpack)(packed)
    assert jax.tree_util.tree_structure(back) == (
        jax.tree_util.tree_structure(values))
    for a, b in zip(jax.tree_util.tree_leaves(back),
                    jax.tree_util.tree_leaves(values)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


def test_packs_once_across_many_executes(monkeypatch):
    calls = []

    def counting(values):
        calls.append(1)
        return pack_values(values)

    monkeypatch.setattr(server, "pack_values", counting)
    values = {"a": jnp.ones((2,)), "b": jnp.ones((2,))}
    model = ServedModel("m", values, lambda v, x, e: x * v["a"][0] + v["b"],
                        lambda b: jnp.ones((b, 2)), 2)
    for _ in range(3):
        for e in range(2):
            for b in (1, 2):
                model.execute(e, b)
    assert len(calls) == 1
    assert len(model.executables) == 4


@pytest.mark.parametrize("values,buffers", [
    ({"a": jnp.ones((2,)), "b": jnp.ones((3,)), "c": jnp.ones((2, 2))}, 4),
    (None, 1),
], ids=["all-shapes-differ", "no-leaves"])
def test_any_pytree_still_serves(values, buffers):
    def forward(v, x, e):
        if v is None:
            return x.sum(axis=1)
        return x.sum(axis=1) * v["a"].sum() + v["b"].sum() + v["c"][e, e]

    model = ServedModel("m", values, forward, lambda b: jnp.ones((b, 2)), 2)
    want = forward(values, jnp.ones((3, 2)), 1)
    np.testing.assert_array_equal(model.execute(1, 3), want)
    assert model.launch_buffers == buffers


def test_engine_counts_the_buffers_of_its_quanta():
    table = ProfileTable.paper_rtx3080().select_models([0, 1]).restrict_exits(
        [0, 3])
    models = [
        ServedModel("m0", {"a": jnp.ones((2,)), "b": jnp.ones((2,))},
                    lambda v, x, e: x.sum(axis=1) + v["a"][0],
                    lambda b: jnp.ones((b, 2)), 2),
        ServedModel("m1", {"a": jnp.ones((2,)), "c": jnp.ones((3,))},
                    lambda v, x, e: x.sum(axis=1) + v["c"][0],
                    lambda b: jnp.ones((b, 2)), 2),
    ]
    sched = make_scheduler("edgeserving", table,
                           SchedulerConfig(slo=10.0, max_batch=4))
    engine = ServingEngine(models, sched)
    engine.warmup()
    arrivals = [Request(req_id=i, model=i % 2, arrival=i * 1e-3)
                for i in range(20)]
    comps, _ = engine.run(arrivals, duration=0.02)
    assert len(comps) == 20
    assert [m.launch_buffers for m in models] == [2, 3]
    quanta = {(c.model, c.dispatch) for c in comps}
    assert engine.counters["batches_served"] == len(quanta)
    assert engine.counters["launch_buffers"] == sum(
        models[m].launch_buffers for m, _ in quanta)
