"""Plain reference of the `resnet50_imagenet` configuration: ResNet-50
(He et al., arXiv:1512.03385) in the form the upstream trains (torchvision's:
the stride of a down-sampling block sits on its 3x3 convolution, and the last
batch-norm scale of every block starts at zero, Goyal et al. arXiv:1706.02677)
on 224x224 inputs that arrive as uint8 pixels and are normalised here with
the ImageNet channel statistics.

Parameters are a flat {path: array} dict under the paths the program's own
parameter tree uses; nothing is read from the program here.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import common as C

_MEAN = (0.485, 0.456, 0.406)
_STD = (0.229, 0.224, 0.225)


def _blocks(cfg: dict):
    """(block index, width, stride, has projection) in order."""
    arch = cfg["arch"]
    cin, j = arch["stem_width"], 0
    for i, (n, width) in enumerate(zip(arch["stage_sizes"],
                                       arch["stage_widths"])):
        for b in range(n):
            stride = 2 if (i > 0 and b == 0) else 1
            out = width * 4
            yield j, cin, width, stride, (stride != 1 or cin != out)
            cin, j = out, j + 1


def _plan(cfg: dict):
    arch = cfg["arch"]
    stem = arch["stem_width"]

    def conv(path, kh, cin, cout):
        return (f"{path}/kernel", (kh, kh, cin, cout),
                math.sqrt(2.0 / (kh * kh * cin)))

    def bn(path, c, scale=1.0):
        return [(f"{path}/scale", (c,), scale), (f"{path}/bias", (c,), 0.0)]

    plan = [conv("Conv_0", 7, arch["input_shape"][-1], stem)]
    plan += bn("BatchNorm_0", stem)
    cin = stem
    for j, cin, width, _stride, proj in _blocks(cfg):
        p, out = f"BottleneckBlock_{j}", width * 4
        plan.append(conv(f"{p}/Conv_0", 1, cin, width))
        plan += bn(f"{p}/BatchNorm_0", width)
        plan.append(conv(f"{p}/Conv_1", 3, width, width))
        plan += bn(f"{p}/BatchNorm_1", width)
        plan.append(conv(f"{p}/Conv_2", 1, width, out))
        plan += bn(f"{p}/BatchNorm_2", out, 0.0)
        if proj:
            plan.append(conv(f"{p}/Conv_3", 1, cin, out))
            plan += bn(f"{p}/BatchNorm_3", out)
        cin = out
    classes = arch["num_classes"]
    plan.append(("Dense_0/kernel", (cin, classes), math.sqrt(1.0 / cin)))
    plan.append(("Dense_0/bias", (classes,), 0.0))
    return plan


def init_params(key, cfg: dict) -> dict:
    out = {}
    for i, (path, shape, init) in enumerate(_plan(cfg)):
        if path.endswith("kernel"):
            out[path] = C.normal_init(jax.random.fold_in(key, i), shape, init)
        else:
            out[path] = jnp.full(shape, init, jnp.float32)
    return out


def param_shapes(cfg: dict) -> dict:
    return {path: shape for path, shape, _ in _plan(cfg)}


def loss(params: dict, batch, cfg: dict, precision: str = "float32"):
    """batch = (pixels uint8 or float [B,H,W,3], labels i32 [B], unused)."""
    x, y, _ = batch
    if x.dtype == jnp.uint8:
        x = (x.astype(jnp.float32) / 255.0
             - jnp.asarray(_MEAN, jnp.float32)) / jnp.asarray(_STD,
                                                               jnp.float32)

    def cbn(x, p, conv, bn, stride, pad):
        x = C.conv(x, params[f"{p}{conv}/kernel"], stride, pad, precision)
        return C.batch_norm(x, params[f"{p}{bn}/scale"],
                            params[f"{p}{bn}/bias"])

    def stem(x):
        x = jax.nn.relu(cbn(x, "", "Conv_0", "BatchNorm_0", 2, 3))
        return C.max_pool(x, 3, 2, 1)

    def block(x, j, stride, proj):
        p = f"BottleneckBlock_{j}/"
        y1 = jax.nn.relu(cbn(x, p, "Conv_0", "BatchNorm_0", 1, 0))
        y1 = jax.nn.relu(cbn(y1, p, "Conv_1", "BatchNorm_1", stride, 1))
        y1 = cbn(y1, p, "Conv_2", "BatchNorm_2", 1, 0)
        if proj:
            x = cbn(x, p, "Conv_3", "BatchNorm_3", stride, 0)
        return jax.nn.relu(y1 + x)

    # the stem and every block recomputed in the backward pass: the same
    # mathematics, and a float32 batch of the timed size fits on one chip
    x = jax.checkpoint(stem)(x)
    for j, _cin, _width, stride, proj in _blocks(cfg):
        x = jax.checkpoint(block, static_argnums=(1, 2, 3))(x, j, stride, proj)
    x = jnp.mean(x, axis=(1, 2))
    logits = C.dense(x, params["Dense_0/kernel"], params["Dense_0/bias"])
    return C.cross_entropy(logits, y)
