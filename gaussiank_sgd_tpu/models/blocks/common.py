"""What every block and every model built from them shares: the weights'
initialiser, the RMSNorm (plain or zero-centred), a sequence seen some
positions back, whether the Pallas kernels run, a model's fields handed to
its layers, the untied head."""

from __future__ import annotations

import dataclasses
import math
import types
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax


def INIT(key, shape, dtype=jnp.float32):
    """normal(0, 0.02), drawn flat and folded to `shape`: entry for entry
    the draw of `shape` itself (the generator counts entries, not rows),
    from a program the TPU compiler is done with in 0.7 s where a draw of
    three axes takes it 3.3 (`[2048, 32, 128]`) to 9.3 (`[8, 2048, 1024]`);
    a model's init program is mostly such draws."""
    return nn.initializers.normal(0.02)(key, (math.prod(shape),),
                                        dtype).reshape(shape)


def scaled_init(by: float):
    """`INIT`, its draw times `by`: a product that writes the residual
    stream in a model whose config says `rescale_prenorm_residual`."""
    return INIT if by == 1.0 else lambda *a: INIT(*a) * by


def use_kernels(kernels: Optional[bool]) -> bool:
    """`kernels` where it is given; else whether the process's default
    backend is a TPU."""
    return jax.default_backend() == "tpu" if kernels is None else kernels


def rms_normed(x, scale, eps: float, dtype, zero_centred: bool = False):
    """`x / rms(x) * scale` over the last axis in float32, rounded once;
    `zero_centred`: times `1 + scale`."""
    x = x.astype(jnp.float32)
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + scale if zero_centred else scale)).astype(dtype)


def norm_scale_init(zero_centred: bool):
    """A norm's scale at init: ones, or zeros where the norm multiplies by
    `1 + scale` (weight decay then pulls the factor to 1 and not to 0)."""
    return nn.initializers.zeros if zero_centred else nn.initializers.ones


class RMSNorm(nn.Module):
    eps: float
    dtype: Any
    zero_centred: bool = False      # `x / rms(x) * (1 + scale)`, scale from 0

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", norm_scale_init(self.zero_centred),
                           (x.shape[-1],), jnp.float32)
        with jax.named_scope("rms_norm"):
            return rms_normed(x, scale, self.eps, self.dtype,
                              self.zero_centred)


def shifted(a, back: int):
    """`a` [B, S, w] as seen `back` positions back: entry t holds
    `a[t - back]`, zeros before the sequence starts (after its end where
    `back` is negative)."""
    if back == 0:
        return a
    s = a.shape[1]
    if back > 0:
        return jnp.pad(a, ((0, 0), (back, 0), (0, 0)))[:, :s]
    return jnp.pad(a, ((0, 0), (0, -back), (0, 0)))[:, -back:]


def own_fields(module: nn.Module) -> types.SimpleNamespace:
    """A model's own fields as a namespace for its layers: a module may not
    be another's field, so the layers get the numbers."""
    return types.SimpleNamespace(**{
        f.name: getattr(module, f.name) for f in dataclasses.fields(module)
        if f.name not in ("parent", "name")})


def untied_head(model: nn.Module, x):
    """`x @ lm_head`, float32 logits under the device scope `lm_head`: the
    leaf `lm_head` [hidden_size, vocab_size] of `model` (float32, multiplied
    as `model.dtype`). For a model that takes its head once."""
    with jax.named_scope("lm_head"):
        head = model.param("lm_head", INIT,
                           (model.hidden_size, model.vocab_size), jnp.float32)
        return jnp.dot(x, head.astype(model.dtype),
                       preferred_element_type=jnp.float32)
