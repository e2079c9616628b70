"""Each configuration's FLOP function against XLA's count, and the
reference's independence from the program."""

import ast
import json
import pathlib

import jax
import jax.numpy as jnp
import pytest

from bench.models import early_exit_resnet as family
from repro.configs.edgeserving_resnets import SMOKE
from repro.models.common import split_params
from repro.models.resnet import EarlyExitResNet

BENCH = pathlib.Path(family.__file__).resolve().parents[1]


def smoke_cfg(image=(16, 16, 3)):
    """The program's SMOKE trio as a benchmark configuration."""
    cfg = json.loads((BENCH / "configs" / "resnet-trio-c100.json").read_text())
    cfg["models"] = [{"name": k, "blocks": list(v.blocks)}
                     for k, v in SMOKE.items()]
    cfg["stage_widths"] = [8, 16, 32, 64]
    cfg["stem"]["width"] = 8
    cfg["image"] = list(image)
    return cfg


@pytest.mark.parametrize("m,e,b", [(0, 0, 1), (1, 2, 2), (2, 3, 3),
                                   (2, 1, 4)])
def test_flops_against_xla_on_the_smoke_trio(m, e, b):
    cfg = smoke_cfg()
    net = EarlyExitResNet(family.program_config(cfg, m))
    values = split_params(net.init(jax.random.key(0)))[0]
    x = jnp.zeros((b, *cfg["image"]), jnp.float32)
    xla = jax.jit(net.forward_exit, static_argnums=2).lower(
        values, x, e).compile().cost_analysis()["flops"]
    # XLA also counts normalisation and activations, which are not matrix
    # work: at the smoke widths (8-64 channels) they are up to a quarter.
    assert 0.75 * xla <= family.flops(cfg, m, e, b) <= xla


@pytest.mark.parametrize("image", [(32, 32, 3), (64, 64, 3)])
@pytest.mark.parametrize("m,e,b", [(0, 3, 1), (2, 3, 4), (1, 0, 2)])
def test_flops_against_xla_at_published_widths(image, m, e, b):
    cfg = json.loads((BENCH / "configs" / "resnet-trio-c100.json").read_text())
    cfg["image"] = list(image)
    net = EarlyExitResNet(family.program_config(cfg, m))
    values = jax.eval_shape(lambda k: split_params(net.init(k))[0],
                            jax.random.key(0))
    x = jax.ShapeDtypeStruct((b, *cfg["image"]), jnp.float32)
    xla = jax.jit(net.forward_exit, static_argnums=2).lower(
        values, x, e).cost_analysis()["flops"]
    # at 256-2048 channels the non-matrix work is a few percent
    assert 0.95 * xla <= family.flops(cfg, m, e, b) <= xla


def test_published_sizes():
    cfg = json.loads((BENCH / "configs" / "resnet-trio-c100.json").read_text())
    assert family.flops(cfg, 2, 3, 1) == pytest.approx(6.9025e9, rel=1e-4)
    assert family.flops(cfg, 2, 0, 1) == pytest.approx(0.4303e9, rel=1e-4)
    cfg["image"], cfg["num_classes"] = [64, 64, 3], 200
    assert family.flops(cfg, 2, 3, 1) == pytest.approx(28.669e9, rel=1e-4)


def test_reference_imports_nothing_of_the_program():
    src = (BENCH / "models" / "early_exit_resnet_ref.py").read_text()
    names = set()
    for node in ast.walk(ast.parse(src)):
        if isinstance(node, ast.Import):
            names.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            names.add(node.module)
    assert names <= {"__future__", "functools", "jax", "jax.numpy", "numpy"}
