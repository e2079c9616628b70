"""The early-exit bottleneck ResNet family, served through the program.

A configuration file of this family gives the trio's stage plans and every
size; this module turns it into the program's ``ServedModel`` objects and
answers what the harness asks of a model family:

* ``build(cfg, seed)``: weights and inputs made on the device in one jitted
  call from the seed (the benchmark makes them, so the reference takes
  nothing the program made), wrapped in ``ServedModel`` around the program's
  ``EarlyExitResNet.forward_exit``;
* ``flops(cfg, m, e, b)``: the multiply-adds of one quantum, times two,
  from the shapes alone;
* ``reference(cfg, weights, inputs, quant)``: every exit's logits from the
  plain float32 reference in ``early_exit_resnet_ref.py``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.models import early_exit_resnet_ref as ref


@dataclasses.dataclass
class Built:
    models: List[Any]                  # program ServedModel, one per entry
    weights: List[Dict[str, Any]]      # bench-made pytrees, same order
    inputs: List[Dict[int, jax.Array]]  # per model: batch size -> images
    batch_sizes: List[int]
    exit_names: List[str]


def batch_sizes(cfg) -> List[int]:
    return list(range(1, cfg["max_batch"] + 1))


def _plan(cfg, m):
    """Yield ``(cin, width, cout, stride)`` for each block of model ``m``,
    stage by stage, as ``(stage, blocks)``."""
    cin = cfg["stem"]["width"]
    stages = []
    for s, n in enumerate(cfg["models"][m]["blocks"]):
        width = cfg["stage_widths"][s]
        cout = width * cfg["expansion"]
        blocks = []
        for i in range(n):
            stride = cfg["stage_strides"][s] if i == 0 else 1
            blocks.append((cin, width, cout, stride))
            cin = cout
        stages.append(blocks)
    return stages


def _leaves(cfg, m):
    """``(path, shape, mean, std)`` of every weight of model ``m``, in the
    program's layout: convolutions with std 1/sqrt(fan_in), norm scales
    1 + 0.1 N(0, 1), norm biases 0.1 N(0, 1), heads with std 1/sqrt(C)."""
    stem = cfg["stem"]

    def conv(path, k, cin, cout):
        return [(path, (k, k, cin, cout), 0.0, 1.0 / math.sqrt(k * k * cin))]

    def norm(path, c):
        return [(path + ("scale",), (c,), 1.0, 0.1),
                (path + ("bias",), (c,), 0.0, 0.1)]

    out = conv(("stem",), stem["kernel"], cfg["image"][2], stem["width"])
    out += norm(("stem_norm",), stem["width"])
    for s, blocks in enumerate(_plan(cfg, m)):
        layer = f"layer{s + 1}"
        for i, (cin, width, cout, stride) in enumerate(blocks):
            p = (layer, i)
            out += conv(p + ("conv1",), 1, cin, width) + norm(p + ("n1",), width)
            out += conv(p + ("conv2",), 3, width, width) + norm(p + ("n2",), width)
            out += conv(p + ("conv3",), 1, width, cout) + norm(p + ("n3",), cout)
            if stride != 1 or cin != cout:
                out += conv(p + ("proj",), 1, cin, cout)
                out += norm(p + ("nproj",), cout)
        c = blocks[-1][2]
        out.append(((f"exit_head{s}",), (c, cfg["num_classes"]), 0.0,
                    1.0 / math.sqrt(c)))
    return out


def _weights(cfg, m, key):
    """All of model ``m``'s weights from one normal draw, cut into leaves
    (one random call keeps tracing and compiling short)."""
    leaves = _leaves(cfg, m)
    sizes = [math.prod(shape) for _, shape, _, _ in leaves]
    flat = jax.random.normal(key, (sum(sizes),), jnp.float32)
    w: Dict[str, Any] = {}
    off = 0
    for (path, shape, mean, std), n in zip(leaves, sizes):
        node = w
        for k, nxt in zip(path[:-1], path[1:]):
            default = [] if isinstance(nxt, int) else {}
            if isinstance(node, list):
                if k == len(node):
                    node.append(default)
                node = node[k]
            else:
                node = node.setdefault(k, default)
        node[path[-1]] = mean + std * flat[off:off + n].reshape(shape)
        off += n
    return w


def seed_words(seed: int) -> np.ndarray:
    """Any whole seed (it may pass 32 bits) as two uint32 words."""
    seed = int(seed) % (1 << 64)
    return np.array([seed & 0xFFFFFFFF, seed >> 32], dtype=np.uint32)


def make_state(cfg, seed: int):
    """Weights and inputs of every model, made on the device in one jitted
    call. Inputs: ``[b, H, W, C]`` standard normal images per batch size."""
    n_models = len(cfg["models"])
    sizes = batch_sizes(cfg)

    def make(words):
        root = jax.random.fold_in(
            jax.random.fold_in(jax.random.key(0), words[0]), words[1])
        weights, inputs = [], []
        for m in range(n_models):
            kw, kx = jax.random.split(jax.random.fold_in(root, m))
            weights.append(_weights(cfg, m, kw))
            inputs.append({b: jax.random.normal(
                jax.random.fold_in(kx, b), (b, *cfg["image"]), jnp.float32)
                for b in sizes})
        return weights, inputs

    return jax.jit(make)(seed_words(seed))


def program_config(cfg, m):
    """The program's ``ResNetConfig`` for entry ``m``; refuses a
    configuration whose widths the program cannot express."""
    from repro.models.resnet import EXPANSION, ResNetConfig

    entry = cfg["models"][m]
    rc = ResNetConfig(
        variant=entry["name"], num_classes=cfg["num_classes"],
        width_multiplier=cfg["stage_widths"][0] / 64.0,
        blocks_override=tuple(entry["blocks"]), groups=cfg["norm"]["groups"])
    if (rc.widths() != list(cfg["stage_widths"])
            or EXPANSION != cfg["expansion"]
            or cfg["stem"] != {"kernel": 3, "stride": 1,
                               "width": cfg["stage_widths"][0]}
            or list(cfg["stage_strides"]) != [1, 2, 2, 2]
            or cfg["norm"]["eps"] != 1e-5):
        raise ValueError(f"{entry['name']}: the program does not serve "
                         "these sizes")
    return rc


def build(cfg, seed: int) -> Built:
    from repro.models.resnet import EarlyExitResNet
    from repro.runtime.server import ServedModel

    weights, inputs = make_state(cfg, seed)
    models = []
    for m, entry in enumerate(cfg["models"]):
        net = EarlyExitResNet(program_config(cfg, m))
        models.append(ServedModel(
            name=entry["name"], values=weights[m],
            forward_fn=net.forward_exit,
            data_fn=inputs[m].__getitem__,
            num_exits=len(cfg["exits"])))
    return Built(models, weights, inputs, batch_sizes(cfg), list(cfg["exits"]))


def _taps(n: int, k: int, stride: int) -> int:
    """Over one spatial axis of a SAME convolution: the (output, tap)
    pairs that fall on the input rather than on its zero padding."""
    out = -(-n // stride)
    lo = max((out - 1) * stride + k - n, 0) // 2
    return sum(1 for i in range(out) for j in range(k)
               if 0 <= i * stride - lo + j < n)


def _conv_flops(h, w, k, stride, cin, cout) -> int:
    return 2 * _taps(h, k, stride) * _taps(w, k, stride) * cin * cout


def flops(cfg, m: int, e: int, b: int) -> float:
    """Twice the multiply-adds of the convolutions and the head of one
    quantum of model ``m`` at exit ``e`` and batch ``b``, counting only
    taps that fall on the input (not on SAME padding). Normalisation,
    activations and pooling are left out: they are not matrix work."""
    h, w, c_img = cfg["image"]
    stem = cfg["stem"]
    total = _conv_flops(h, w, stem["kernel"], 1, c_img, stem["width"])
    for blocks in _plan(cfg, m)[:e + 1]:
        for cin, width, cout, stride in blocks:
            total += _conv_flops(h, w, 1, 1, cin, width)
            total += _conv_flops(h, w, 3, stride, width, width)
            if stride != 1 or cin != cout:
                total += _conv_flops(h, w, 1, stride, cin, cout)
            h, w = -(-h // stride), -(-w // stride)
            total += _conv_flops(h, w, 1, 1, width, cout)
    cout = _plan(cfg, m)[e][-1][2]
    total += 2 * cout * cfg["num_classes"]
    return float(b * total)


def reference(cfg, weights, inputs: Dict[int, jax.Array], quant=None):
    """``{b: [logits of exit 0..E-1, each [b, classes]]}`` for one model's
    inputs, from one reference pass over all batch sizes' images."""
    sizes = sorted(inputs)
    x = jnp.concatenate([inputs[b] for b in sizes])
    exits = ref.all_exits(weights, x, cfg, quant)
    out, row = {}, 0
    for b in sizes:
        out[b] = [logits[row:row + b] for logits in exits]
        row += b
    return out
