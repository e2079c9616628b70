"""Plain float32 reference of the early-exit bottleneck ResNet.

Written from the published description (He et al. 2016, bottleneck
blocks with the stride on the 3x3 convolution; GroupNorm, Wu & He 2018,
in place of BatchNorm; one pooled linear head after each stage) and from
the configuration file alone. It imports nothing of the program under test.

Weights are a nested dict:

    stem [3,3,3,C0], stem_norm {scale, bias},
    layer1..layer4: list of blocks {conv1, n1, conv2, n2, conv3, n3,
                                    and proj, nproj where the shape changes},
    exit_head0..exit_head3 [C_s, classes]

Convolutions run at ``Precision.HIGHEST`` (true float32 on a TPU). Two
lower precisions stand in for the program to test the comparison:

* ``quant="bf16"``: weights, inputs and every activation stored in
  bfloat16 (norms and the residual stream included): the model cast to
  bfloat16 whole;
* ``quant="fp8"``: both operands of every convolution and of the head cast
  to float8_e4m3 with a per-tensor scale first, the step below bfloat16
  operands.

``quant="default"`` keeps float32 storage and runs the convolutions and
heads at JAX's default precision, as the program is served: a second
reference, for the readings of ``bench/control.py`` only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
_FP8 = jnp.float8_e4m3fn
_FP8_MAX = 448.0


def _quantize(x, quant):
    """``x`` rounded through ``quant`` and scaled back (float32 result)."""
    if quant in (None, "default"):
        return x
    assert quant == "fp8", quant
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _FP8_MAX
    return (x / scale).astype(_FP8).astype(jnp.float32) * scale


def _precision(quant):
    return None if quant == "default" else HIGHEST


def conv(x, w, stride, quant=None):
    return jax.lax.conv_general_dilated(
        _quantize(x, quant), _quantize(w, quant), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=_precision(quant))


def group_norm(x, p, groups, eps):
    b, h, w, c = x.shape
    g = min(groups, c)
    while c % g:
        g -= 1
    xg = x.reshape(b, h, w, g, c // g)
    mu = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = ((xg - mu) ** 2).mean(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mu) / jnp.sqrt(var + eps)
    return xg.reshape(b, h, w, c) * p["scale"] + p["bias"]


@functools.partial(jax.jit, static_argnames=("groups", "eps", "quant"))
def stem(w, p, x, groups, eps, quant):
    return jax.nn.relu(group_norm(conv(x, w, 1, quant), p, groups, eps))


@functools.partial(jax.jit,
                   static_argnames=("stride", "groups", "eps", "quant"))
def bottleneck(blk, x, stride, groups, eps, quant):
    h = jax.nn.relu(group_norm(conv(x, blk["conv1"], 1, quant), blk["n1"],
                               groups, eps))
    h = jax.nn.relu(group_norm(conv(h, blk["conv2"], stride, quant),
                               blk["n2"], groups, eps))
    h = group_norm(conv(h, blk["conv3"], 1, quant), blk["n3"], groups, eps)
    if "proj" in blk:
        x = group_norm(conv(x, blk["proj"], stride, quant), blk["nproj"],
                       groups, eps)
    return jax.nn.relu(x + h)


@functools.partial(jax.jit, static_argnames=("quant",))
def head(w, x, quant):
    pooled = x.mean(axis=(1, 2))
    return jnp.dot(_quantize(pooled, quant), _quantize(w, quant),
                   precision=_precision(quant))


def all_exits(weights, x, cfg, quant=None):
    """Logits of every exit for images ``x`` [n, H, W, 3], as numpy.

    One pass through the trunk, block by block, reading each stage's head
    on the way; GroupNorm is per image, so images of different batches can
    share the pass. Returns a list of ``[n, classes]`` float32 arrays,
    shallowest exit first.
    """
    groups, eps = cfg["norm"]["groups"], cfg["norm"]["eps"]
    if quant == "bf16":
        weights, x = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                                  (weights, x))
        quant = None
    h = stem(weights["stem"], weights["stem_norm"], x, groups, eps, quant)
    out = []
    for s, first_stride in enumerate(cfg["stage_strides"]):
        for i, blk in enumerate(weights[f"layer{s + 1}"]):
            stride = first_stride if i == 0 else 1
            h = bottleneck(blk, h, stride, groups, eps, quant)
        logits = head(weights[f"exit_head{s}"], h, quant)
        out.append(np.asarray(logits.astype(jnp.float32)))
    return out
