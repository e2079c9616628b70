"""The paper's deployment as served models: early-exit ResNets behind
``ServingEngine`` (paper Sec. IV-A, VI-A).

There are no trained CIFAR-100 weights in the repository, so weights are
random, made from a seed; so are the inputs. Both are made on the device.
"""

from __future__ import annotations

from typing import List, Mapping

import jax
import jax.numpy as jnp

from repro.models.common import split_params
from repro.models.resnet import EarlyExitResNet, ResNetConfig
from repro.runtime.server import ServedModel

__all__ = ["IMAGE_SHAPE", "served_resnets"]

IMAGE_SHAPE = (32, 32, 3)   # CIFAR-100, NHWC


def _inputs(key: jax.Array):
    """``data_fn``: a ``[b, 32, 32, 3]`` float32 batch per batch size,
    drawn once from ``key`` and then kept on the device."""
    made = {}

    def data_fn(b: int) -> jax.Array:
        if b not in made:
            made[b] = jax.random.normal(jax.random.fold_in(key, b),
                                        (b, *IMAGE_SHAPE), jnp.float32)
        return made[b]

    return data_fn


def served_resnets(configs: Mapping[str, ResNetConfig],
                   seed: int) -> List[ServedModel]:
    """One :class:`ServedModel` per entry of ``configs`` (e.g.
    ``configs.edgeserving_resnets.FULL``), in order, named by its key.
    Model ``i`` draws its weights and inputs from ``fold_in(key(seed), i)``.
    """
    root = jax.random.key(seed)
    models = []
    for i, (name, cfg) in enumerate(configs.items()):
        k_weights, k_inputs = jax.random.split(jax.random.fold_in(root, i))
        net = EarlyExitResNet(cfg)
        values = jax.jit(lambda k, _net=net: split_params(_net.init(k))[0])(
            k_weights)
        models.append(ServedModel(
            name=name, values=values, forward_fn=net.forward_exit,
            data_fn=_inputs(k_inputs), num_exits=cfg.num_exits))
    return models
