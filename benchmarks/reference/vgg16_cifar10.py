"""Plain reference of the `vgg16_cifar10` configuration: VGG-16 with batch
normalisation on 32x32 inputs (Simonyan & Zisserman, arXiv:1409.1556,
configuration D; the CIFAR variant of the upstream's `models/vgg.py`:
thirteen 3x3 convolutions without bias, each followed by batch norm and ReLU,
five 2x2 max-pools, one 512-wide hidden layer with dropout, a linear head).

Parameters are a flat {path: array} dict. The paths are the ones the program's
own parameter tree uses, so the harness can hand the benchmark's weights to
the program leaf by leaf; nothing is read from the program here.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import common as C


def _plan(cfg: dict):
    """(path, shape, init std or constant) for every parameter, in order of
    use. `cfg["arch"]["stages"]` is the layer list of the configuration's
    file: an int is a 3x3 convolution's width, "M" a max-pool."""
    arch = cfg["arch"]
    cin, i, plan = arch["input_shape"][-1], 0, []
    for v in arch["stages"]:
        if v == "M":
            continue
        plan.append((f"Conv_{i}/kernel", (3, 3, cin, v),
                     math.sqrt(2.0 / (9 * cin))))
        plan.append((f"BatchNorm_{i}/scale", (v,), 1.0))
        plan.append((f"BatchNorm_{i}/bias", (v,), 0.0))
        cin, i = v, i + 1
    hidden, classes = arch["hidden"], arch["num_classes"]
    plan.append(("Dense_0/kernel", (cin, hidden), math.sqrt(1.0 / cin)))
    plan.append(("Dense_0/bias", (hidden,), 0.0))
    plan.append(("Dense_1/kernel", (hidden, classes),
                 math.sqrt(1.0 / hidden)))
    plan.append(("Dense_1/bias", (classes,), 0.0))
    return plan


def init_params(key, cfg: dict) -> dict:
    """Seeded weights: He-normal convolutions, unit batch-norm scales, zero
    biases. Trace it under one `jax.jit`: one program, made on the device."""
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
    """batch = (images f32 [B,32,32,3], labels i32 [B], dropout keep-mask
    f32 [B, hidden] already divided by the keep probability, or None)."""
    x, y, keep = batch
    x = x.astype(jnp.float32)

    def stage(x, layers):
        for i in layers:
            x = C.conv(x, params[f"Conv_{i}/kernel"], 1, 1, precision)
            x = jax.nn.relu(C.batch_norm(x, params[f"BatchNorm_{i}/scale"],
                                         params[f"BatchNorm_{i}/bias"]))
        return C.max_pool(x, 2, 2)

    # one stage (the convolutions up to a pool) at a time, recomputed in the
    # backward pass: the same mathematics, and a float32 batch of the timed
    # size fits beside nothing else on one chip
    i, layers = 0, []
    for v in cfg["arch"]["stages"]:
        if v == "M":
            x = jax.checkpoint(stage, static_argnums=1)(x, tuple(layers))
            layers = []
        else:
            layers.append(i)
            i += 1
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(C.dense(x, params["Dense_0/kernel"],
                            params["Dense_0/bias"], precision))
    if keep is not None:
        x = x * keep
    # the head is float32 in the configuration too
    logits = C.dense(x, params["Dense_1/kernel"], params["Dense_1/bias"])
    return C.cross_entropy(logits, y)
