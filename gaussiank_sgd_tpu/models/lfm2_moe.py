"""LFM2-8B-A1B (LiquidAI/LFM2-8B-A1B, config.json, `model_type` `lfm2_moe`,
8.3B-A1.5B): a decoder-only language model whose layers mix positions by a
gated short convolution, three layers in four, and by grouped-query
attention with normed heads in the fourth; two leading layers with a dense
MLP, then 32 sparse experts chosen by a sigmoid router with a selection
bias; the head is the embedding. Defaults are the published widths: hidden
2048, a convolution of 3 taps, 32 query and 8 key/value heads of 64, experts
of 1792 with 4 a token, a dense layer of 7168, 24 layers.

Per layer, pre-norm (RMSNorm, eps 1e-5, no bias anywhere; `h` a token's
stream), as transformers' `modeling_lfm2_moe.py` has it:

    h' = h + op(operator_norm(h));   y = h' + ffn(ffn_norm(h'))

    conv:            [B | C | x] = W_in u            (2048 -> 3 x 2048)
                     v = B * x
                     c_t = k_0 v_{t-2} + k_1 v_{t-1} + k_2 v_t   a channel
                     op = W_out (C * c)
        (`k` a `[2048, 3]` leaf: a causal depthwise convolution, zeros
        before the sequence starts, no bias, no activation)
    full_attention:  q, k, v = W_q u, W_k u, W_v u   32 / 8 / 8 heads of 64
                     q, k = rope(norm_q(q)), rope(norm_k(k))
        (RMSNorm over the 64 of each head, one learned scale of 64 each,
        BEFORE the turn; half-split rotary, theta 1e6; causal, scale 1/8)
                     op = W_o attention(q, k, v)
    ffn, the first `num_dense_layers`:  W2 (silu(W1 x) * W3 x), 7168 wide
    ffn, the others: s = sigmoid(x W_r) in float32;  C = top4(s + b);
                     sum over e in C of (s_e / (sum_C s + 1e-6)) F_e(x)

`b` (`router_bias`, the published `expert_bias`) is a leaf of `params`,
zero at init; it only selects, so its gradient is exactly zero. The rule
that moves it by the experts' load is NOT built (the config gives no speed
for it). After the last layer a RMSNorm (`embedding_norm`) and the head,
which is the embedding transposed: ONE leaf, `embed/embedding`, with
gradient by both paths.

`layer_types` names the layers held here, in order (a pipeline stage holds
some of the published 24), the first `num_dense_layers` of them with the
dense MLP. One chip's share, the experts' layer, attention (splash
attention on a TPU, blocks of queries elsewhere), RMSNorm, the rotary
helper and rematerialisation are `models/blocks/`'s, imported.

The taps are shifted multiply-adds, not a convolution primitive and not a
kernel: XLA fuses them with both gates into one pass over `[tokens, 2048]`
forward and one backward (`gated_taps`, which says what its backward pass
is so that every shift is of an input).

Device scopes: `short_conv` around the mixer with `conv_in_proj`,
`conv_gate` (both gates and the taps) and `conv_out_proj` beneath it;
`attn_full` and `attn_proj` (`qk_norm` and `rope` inside it) from
`attention.Attention`; `moe_router`, `moe_experts` (with `blocks/experts.py`'s
scopes inside both), `dense_mlp`, `lm_head`, `embed`, `rms_norm` from
`common.RMSNorm`. Counters as `blocks/experts.py`'s: `moe_held_assignments`,
`moe_room_used`, `moe_load_max_over_mean`, `moe_tokens_unserved`.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from .blocks.attention import FULL, Attention, recomputed
from .blocks.common import INIT, RMSNorm, own_fields, shifted
from .blocks.experts import Experts, GatedMLP, model_counters
from .blocks.rope import rope_inv_freq

CONV = "conv"
_SUM_EPS = 1e-6         # in the chosen scores' sum (`norm_topk_prob`)
# the published pattern: an attention layer after every two or three
# convolution layers, six of them in 24
_PUBLISHED = tuple(
    FULL if i in (2, 6, 10, 14, 18, 21) else CONV for i in range(24))


def _gate_parts(bcx, back: int = 0):
    """`B`, `C`, `x` in float32 from `bcx = [B | C | x]`, as seen `back`
    positions back. The shift is of `bcx` ITSELF, before anything is
    computed from it: XLA fuses a shifted argument into the pass that reads
    it, and writes a shifted intermediate (a converted part, the product
    `B * x`) out in float32 first."""
    return (part.astype(jnp.float32)
            for part in jnp.split(shifted(bcx, back), 3, axis=-1))


def _seen(bcx, back: int):
    """`(B * x)` as seen `back` positions back."""
    gate_in, _, x = _gate_parts(bcx, back)
    return gate_in * x


@jax.custom_vjp
def gated_taps(bcx, kernel):
    """`C * c` with `c_t = sum_j kernel[:, j] * (B * x)_{t - (L - 1 - j)}`
    from `bcx = [B | C | x]` [B, S, 3 h] and the taps `kernel` [h, L]: both
    gates and the causal depthwise convolution, in float32, rounded once to
    `bcx.dtype`. One pass over `bcx` forward. Its backward pass is written
    out (below) so that there too every shift is of an argument, `bcx` or
    the cotangent: differentiated as written, the shifted products and
    their cotangents cross memory in float32 between four fusions. It keeps
    `bcx` and the taps and computes `c` again."""
    taps = kernel.shape[1]
    c = sum(kernel[:, taps - 1 - d] * _seen(bcx, d) for d in range(taps))
    _, gate_out, _ = _gate_parts(bcx)
    return (gate_out * c).astype(bcx.dtype)


def _gated_taps_fwd(bcx, kernel):
    return gated_taps(bcx, kernel), (bcx, kernel)


def _gated_taps_bwd(res, g):
    bcx, kernel = res
    taps = kernel.shape[1]
    gate_in, gate_out, x = _gate_parts(bcx)
    seen = [_seen(bcx, d) for d in range(taps)]
    c = sum(kernel[:, taps - 1 - d] * seen[d] for d in range(taps))
    # the cotangent of `v = B * x` at t: from every later position that saw
    # it, `g * C` there
    dv = 0.0
    for d in range(taps):
        _, later_out, _ = _gate_parts(bcx, -d)
        dv = dv + kernel[:, taps - 1 - d] * (
            shifted(g, -d).astype(jnp.float32) * later_out)
    g = g.astype(jnp.float32)
    dc = g * gate_out
    dkernel = jnp.stack([jnp.sum(dc * seen[taps - 1 - j], axis=(0, 1))
                         for j in range(taps)], axis=-1)
    dbcx = jnp.concatenate([dv * x, g * c, dv * gate_in], axis=-1)
    return dbcx.astype(bcx.dtype), dkernel.astype(kernel.dtype)


gated_taps.defvjp(_gated_taps_fwd, _gated_taps_bwd)


class ShortConv(nn.Module):
    m: Any                          # the model's own fields, as a namespace

    @nn.compact
    def __call__(self, x):
        m = self.m
        hidden = x.shape[-1]
        with jax.named_scope("short_conv"):
            with jax.named_scope("conv_in_proj"):
                bcx = nn.Dense(3 * hidden, use_bias=False, dtype=m.dtype,
                               kernel_init=INIT, name="in_proj")(x)
            with jax.named_scope("conv_gate"):
                kernel = self.param("taps", INIT, (hidden, m.conv_taps),
                                    jnp.float32)
                # a pass of its own: left to itself XLA runs it inside
                # `out_proj`'s product, under that product's name
                y = lax.optimization_barrier(gated_taps(bcx, kernel))
            with jax.named_scope("conv_out_proj"):
                return nn.Dense(hidden, use_bias=False, dtype=m.dtype,
                                kernel_init=INIT, name="out_proj")(y)


class Layer(nn.Module):
    m: Any
    kind: str                       # CONV or FULL
    dense: bool                     # a leading layer without experts

    @nn.compact
    def __call__(self, x):
        m = self.m
        h = RMSNorm(m.rms_norm_eps, m.dtype, name="operator_norm")(x)
        if self.kind == CONV:
            x = x + ShortConv(m, name="conv")(h)
        else:
            x = x + Attention(
                m.num_heads, m.num_kv_heads, m.head_dim, None,
                tuple(rope_inv_freq(m.head_dim, m.rope_theta).tolist()), 1.0,
                m.kernels, m.dtype, qk_norm=True,
                qk_norm_eps=m.rms_norm_eps, name="attn")(h)
        h = RMSNorm(m.rms_norm_eps, m.dtype, name="ffn_norm")(x)
        if self.dense:
            return x + GatedMLP(m.dense_width, "dense_mlp", name="mlp")(h), {}
        y, counters = Experts(
            m.num_experts, m.experts_per_token, m.expert_width,
            m.expert_share, m.expert_shares, m.dtype, scoring="sigmoid",
            select_bias=True, scale=m.routed_scaling_factor,
            sum_eps=_SUM_EPS, kernels=m.kernels, name="moe")(h)
        return x + y, counters


class LFM2MoE(nn.Module):
    vocab_size: int = 65536         # embedding (and head) rows held here
    hidden_size: int = 2048
    num_layers: int = 24
    layer_types: Optional[Tuple[str, ...]] = None   # None: the published
    num_dense_layers: int = 2       # leading layers with a dense MLP
    dense_width: int = 7168
    conv_taps: int = 3              # `conv_L_cache`
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 1000000.0
    num_experts: int = 32           # the router's width, never cut
    experts_per_token: int = 4
    expert_width: int = 1792
    routed_scaling_factor: float = 1.0
    expert_share: int = 0           # which share of the experts is held,
    expert_shares: int = 1          # of how many
    rms_norm_eps: float = 1e-5
    kernels: Optional[bool] = None  # None: where the backend is a TPU
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, tokens, train: bool = True,
                 return_counters: bool = False):
        # tokens int32 [B, S] -> logits float32 [B, S, vocab_size]
        kinds = tuple(self.layer_types or _PUBLISHED[:self.num_layers])
        if len(kinds) != self.num_layers or set(kinds) - {CONV, FULL}:
            raise ValueError(f"{self.num_layers} layers, layer_types "
                             f"{self.layer_types}")
        # unit embeddings, as `Mellum2`'s: the stream has to carry the
        # token. The same leaf is the head, so at seeded weights a token's
        # own logit is about `hidden_size` (the configuration's `assumed`)
        embed = nn.Embed(self.vocab_size, self.hidden_size, dtype=self.dtype,
                         embedding_init=nn.initializers.normal(1.0),
                         name="embed")
        with jax.named_scope("embed"):
            x = embed(tokens)
        layer = recomputed(Layer)
        widths = own_fields(self)
        per_layer = []
        for i, kind in enumerate(kinds):
            dense = i < self.num_dense_layers
            x, counters = layer(widths, kind, dense, name=f"layers_{i}")(x)
            if not dense:
                per_layer.append(counters)
        x = RMSNorm(self.rms_norm_eps, self.dtype, name="embedding_norm")(x)
        with jax.named_scope("lm_head"):
            logits = jnp.einsum("bsh,vh->bsv", x,
                                embed.embedding.astype(self.dtype),
                                preferred_element_type=jnp.float32)
        if not return_counters:
            return logits
        return logits, (model_counters(per_layer) if per_layer else {})
