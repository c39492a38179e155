"""Trinity-Mini (arcee-ai/Trinity-Mini, config.json, `model_type` `afmoe`,
26B-A3B): a decoder-only language model that mixes window attention under
rotary positions three layers in four with full attention under NO
positions in the fourth, gates every head's attention output by a sigmoid
of the layer's input, norms each branch going in AND coming out, and after
two leading dense layers routes every token to 8 of 128 experts by a
sigmoid router beside one shared expert. Defaults are the published widths:
hidden 2048, 32 query and 4 key/value heads of 128, a window of 2048 keys,
experts of 1024, a dense layer of 6144, 32 layers `sliding, sliding,
sliding, full`, an untied head over 200 192 rows.

Per layer `i` (RMSNorm eps 1e-5, no bias anywhere; `x` a token's stream).
What the published config has no key for is from transformers'
`modeling_afmoe.py` as the benchmark's configuration lists it under
`assumed`:

    q = turn_i(norm_q(Wq n_in(x))) / sqrt(128),  k = turn_i(norm_k(Wk n_in(x)))
    a = n_post_attn(Wo (sigmoid(Wg n_in(x)) * attention(q, k, Wv n_in(x))))
    h = x + a
    f = n_post_mlp(mlp_i(n_pre_mlp(h)));         y = h + f

`norm_q`, `norm_k`: an RMSNorm over each head's 128 entries with one learned
scale each, before the turn. `turn_i` is half-split rotary at theta 10 000
in a `sliding_attention` layer (keys `0 <= i - j < 2048`) and the IDENTITY
in a `full_attention` layer (all earlier keys, no positions). `Wg` is a
fifth projection, hidden -> heads x 128, its sigmoid taken entry by entry of
the attention's `[heads, 128]` output. `mlp_i` is `W2 (silu(W1 .) * W3 .)`
of width 6144 in the first `num_dense_layers` layers; after them a shared
gated expert of width `num_shared_experts` x 1024 plus the routed sum: `s =
sigmoid(n_pre_mlp(h) Wr)` in float32 over all 128, the top 8 by `s + b`,
weights `s` alone over (their sum + 1e-20) (`route_norm`), times
`route_scale` 2.826. `b` (`moe/router_bias`, the published `expert_bias`) is
a leaf that only selects, zero at init; the rule that moves it by the
experts' load and the balance loss are NOT built. The stream starts at
`embedding[token] * sqrt(hidden_size)` (`mup_enabled`); after the last layer
a RMSNorm and the head.

`layer_types` names the layers held here, in order (a pipeline stage holds
some of the published 32), the first `num_dense_layers` of them with the
dense MLP. One chip's share, attention (`Attention(gate=True,
positions=...)`), the experts' layer, RMSNorm, the head and
rematerialisation are `models/blocks/`'s, imported.

Device scopes: `attn_window`, `attn_full`, `attn_proj` (with `qk_norm`,
`attn_gate` and, in a window layer only, `rope` inside it), `moe_router`,
`moe_experts`, `moe_shared`, `dense_mlp`, `lm_head`, `embed` (the scale
inside it), `rms_norm` (all four norms, the final one too). Counters as
`blocks/experts.py`'s and `attn_gate_mean`: the mean of the gate's sigmoid over
tokens, heads and layers (0.5 at seeded weights; a gate that saturates
shows there before it shows in the loss).
"""

from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from .blocks.attention import FULL, PERIOD, SLIDING, Attention, recomputed
from .blocks.common import RMSNorm, own_fields, untied_head
from .blocks.experts import Experts, GatedMLP, model_counters
from .blocks.rope import rope_inv_freq

_SUM_EPS = 1e-20        # in the chosen scores' sum (`route_norm`)


class Layer(nn.Module):
    m: Any                          # the model's own fields, as a namespace
    window: Optional[int]           # None: a full layer, without positions
    dense: bool                     # a leading layer without experts

    @nn.compact
    def __call__(self, x):
        m = self.m

        def norm(name):
            return RMSNorm(m.rms_norm_eps, m.dtype, name=name)

        a, gate_mean = Attention(
            m.num_heads, m.num_kv_heads, m.head_dim, self.window,
            tuple(rope_inv_freq(m.head_dim, m.rope_theta).tolist()), 1.0,
            m.kernels, m.dtype, qk_norm=True, qk_norm_eps=m.rms_norm_eps,
            positions=self.window is not None, gate=True,
            name="attn")(norm("input_norm")(x))
        x = x + norm("post_attn_norm")(a)
        h = norm("pre_mlp_norm")(x)
        if self.dense:
            f, counters = GatedMLP(m.dense_width, "dense_mlp",
                                   name="mlp")(h), {}
        else:
            f, counters = Experts(
                m.num_experts, m.experts_per_token, m.expert_width,
                m.expert_share, m.expert_shares, m.dtype, scoring="sigmoid",
                select_bias=True, scale=m.route_scale,
                shared_width=m.num_shared_experts * m.expert_width,
                sum_eps=_SUM_EPS, kernels=m.kernels, name="moe")(h)
        return x + norm("post_mlp_norm")(f), (counters, gate_mean)


class Afmoe(nn.Module):
    vocab_size: int = 200192        # embedding and head rows held here
    hidden_size: int = 2048
    num_layers: int = 32
    layer_types: Optional[Tuple[str, ...]] = None   # None: the published
    num_dense_layers: int = 2       # leading layers with a dense MLP
    dense_width: int = 6144
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    num_experts: int = 128          # the router's width, never cut
    experts_per_token: int = 8
    expert_width: int = 1024
    num_shared_experts: int = 1
    route_scale: float = 2.826
    expert_share: int = 0           # which share of the experts is held,
    expert_shares: int = 1          # of how many
    rms_norm_eps: float = 1e-5
    mup_enabled: bool = True        # the stream starts at sqrt(hidden) times
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
        # unit embeddings, as `Mellum2`'s: the stream has to carry the
        # token. At the products' 0.02 it starts at 0.9 an entry, every
        # branch leaves its norm at 1.0 an entry with most of that common
        # to all tokens, and the seeded routers send every token the same
        # way (the fullest held expert got 2.5 to 4.3 times the mean)
        with jax.named_scope("embed"):
            x = nn.Embed(self.vocab_size, self.hidden_size,
                         embedding_init=nn.initializers.normal(1.0),
                         name="embed")(tokens)
            if self.mup_enabled:
                x = x * math.sqrt(self.hidden_size)
            x = x.astype(self.dtype)
        layer = recomputed(Layer)
        widths = own_fields(self)
        per_layer, gate_means = [], []
        for i, kind in enumerate(kinds):
            dense = i < self.num_dense_layers
            x, (counters, gate_mean) = layer(
                widths, self.sliding_window if kind == SLIDING else None,
                dense, name=f"layers_{i}")(x)
            gate_means.append(gate_mean)
            if not dense:
                per_layer.append(counters)
        x = RMSNorm(self.rms_norm_eps, self.dtype, name="norm")(x)
        logits = untied_head(self, x)
        if not return_counters:
            return logits
        counters = model_counters(per_layer) if per_layer else {}
        return logits, {**counters,
                        "attn_gate_mean": jnp.mean(jnp.stack(gate_means))}
