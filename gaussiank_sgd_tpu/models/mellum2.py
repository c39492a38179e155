"""Mellum 2 (JetBrains/Mellum2-12B-A2.5B-Instruct): a decoder-only language
model with sparse experts in every layer and window and full attention
mixed three to one. Defaults are the published widths (config.json of the
source): hidden 2304, 32 query and 4 key/value heads of 128, 64 experts of
896 with 8 a token, window 1024, 28 layers `sliding, sliding, sliding, full`.

Per layer, pre-norm (RMSNorm, eps 1e-6, no biases anywhere):

    h = x + Wo . attention(rope(Wq n1(x)), rope(Wk n1(x)), Wv n1(x))
    y = h + sum_e p_e W2_e (silu(W1_e n2(h)) * W3_e n2(h))

Attention is causal, each key/value head serving 8 query heads; a
`sliding_attention` layer sees keys `0 <= i - j < window` under plain rotary
positions, a `full_attention` layer all earlier keys under yarn-scaled
ones. `p = softmax(n2(h) Wr)` over all experts in float32, its
`experts_per_token` largest kept and renormalised.

`expert_share` of `expert_shares` names the experts held here (one chip's
share of an expert group, `blocks/experts.py`); `vocab_size` is the number
of embedding and head rows held: a sliced vocabulary is a smaller
vocabulary.

Attention, the rotary turn (against two sets of tables here, default and
yarn), the experts' layer, RMSNorm, the head and rematerialisation are
`models/blocks/`'s, imported; `blocks/__init__.py` lists their device
scopes and counters (this file adds `embed`), what they offer beyond this
model and which model sets which field. Not in this model's published
config and so not used by it: an auxiliary load-balance loss, nor any of
those offers; the benchmark's configuration file lists what is assumed
under `assumed`.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from .blocks.attention import FULL, PERIOD, SLIDING, Attention, recomputed
from .blocks.common import RMSNorm, own_fields, untied_head
from .blocks.experts import Experts, model_counters
from .blocks.rope import rope_inv_freq, yarn_inv_freq


class Layer(nn.Module):
    m: Any                          # the model's own fields, as a namespace
    window: Optional[int]

    @nn.compact
    def __call__(self, x):
        m = self.m
        if self.window:
            inv_freq, scale = rope_inv_freq(m.head_dim, m.rope_theta), 1.0
        else:
            inv_freq = yarn_inv_freq(m.head_dim, m.rope_theta, m.yarn_factor,
                                     m.yarn_original_max, m.yarn_beta_fast,
                                     m.yarn_beta_slow)
            scale = m.yarn_attention_factor
        h = RMSNorm(m.rms_norm_eps, m.dtype, name="input_norm")(x)
        x = x + Attention(m.num_heads, m.num_kv_heads, m.head_dim,
                          self.window, tuple(inv_freq.tolist()), scale,
                          m.kernels, m.dtype, name="attn")(h)
        h = RMSNorm(m.rms_norm_eps, m.dtype, name="post_attn_norm")(x)
        y, counters = Experts(m.num_experts, m.experts_per_token,
                              m.expert_width, m.expert_share,
                              m.expert_shares, m.dtype, kernels=m.kernels,
                              name="moe")(h)
        return x + y, counters


class Mellum2(nn.Module):
    vocab_size: int = 98304         # embedding and head rows held here
    hidden_size: int = 2304
    num_layers: int = 28
    layer_types: Optional[Tuple[str, ...]] = None   # None: the period above
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 1024
    num_experts: int = 64           # the router's width, never cut
    experts_per_token: int = 8
    expert_width: int = 896
    expert_share: int = 0           # which share of the experts is held,
    expert_shares: int = 1          # of how many
    rms_norm_eps: float = 1e-6
    rope_theta: float = 500000.0
    yarn_factor: float = 16.0
    yarn_original_max: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.2772588722239782
    kernels: Optional[bool] = None  # None: where the backend is a TPU
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, tokens, train: bool = True,
                 return_counters: bool = False):
        # tokens int32 [B, S] -> logits float32 [B, S, vocab_size]
        kinds = tuple(self.layer_types or PERIOD * (self.num_layers // 4 + 1)
                      )[:self.num_layers]
        if len(kinds) != self.num_layers or set(kinds) - {SLIDING, FULL}:
            raise ValueError(f"{self.num_layers} layers, layer_types "
                             f"{self.layer_types}")
        # unit embeddings: at the products' 0.02 a layer's output swamps
        # them at random weights, every token's router input then shares
        # one direction and the experts' load collapses onto a few
        with jax.named_scope("embed"):
            x = nn.Embed(self.vocab_size, self.hidden_size, dtype=self.dtype,
                         embedding_init=nn.initializers.normal(1.0),
                         name="embed")(tokens)
        layer = recomputed(Layer)
        widths = own_fields(self)
        per_layer = []
        for i, kind in enumerate(kinds):
            x, counters = layer(
                widths, self.sliding_window if kind == SLIDING else None,
                name=f"layers_{i}")(x)
            per_layer.append(counters)
        x = RMSNorm(self.rms_norm_eps, self.dtype, name="norm")(x)
        logits = untied_head(self, x)
        if not return_counters:
            return logits
        return logits, model_counters(per_layer)
