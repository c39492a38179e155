"""Qwen3-Next-80B-A3B (Qwen/Qwen3-Next-80B-A3B-Instruct, config.json,
`model_type` `qwen3_next`, 80B-A3B): a decoder-only language model whose
layers mix positions by a gated delta rule (a linear attention that carries
a state of 128 x 128 a head along the sequence) three layers in four and by
gated softmax attention at heads of 256 in the fourth; every layer routes
each token to 10 of 512 experts of 512 beside a gated shared expert.
Defaults are the published widths: hidden 2048; 16 key and 32 value heads of
128 under a convolution of 4 taps; 16 query and 2 key/value heads of 256,
the first 64 entries turned at theta 1e7; 48 layers `linear, linear, linear,
full`; an untied head over 151 936 rows.

The equations, from transformers' `modeling_qwen3_next.py`
(`Qwen3NextDecoderLayer`, `Qwen3NextGatedDeltaNet`, `Qwen3NextAttention`,
`Qwen3NextSparseMoeBlock`, `Qwen3NextRMSNorm`, `Qwen3NextRMSNormGated`) and
Yang, Kautz and Hatamizadeh, "Gated Delta Networks" (arXiv:2412.06464); `u`
a normed token, eps 1e-6, no bias anywhere. `norm` is ZERO-CENTRED: `x /
rms(x) * (1 + w)`, `w` from zero (the layers' two norms, the final norm, the
q and k head norms). What departs from the published arrangement is in the
benchmark's configuration under `assumed`.

    h = x + mixer_i(norm_1(x));      y = h + moe(norm_2(h))
    layer i is `full_attention` where (i + 1) % 4 == 0, else
    `linear_attention`; every layer has experts.

`linear_attention` (`blocks/delta.py`; 16 key heads and 32 value heads of
128: key width 2048, value width 4096):

    1. [q | k | v | z] = W_qkvz u   (2048 -> 2048 + 2048 + 4096 + 4096),
       [b | a] = W_ba u             (2048 -> 32 + 32)
       (the published matrices interleave these by key head; held apart
       here they are the same products by a permutation of columns)
    2. [q | k | v] (8192 channels) through a depthwise causal convolution
       of 4 taps, no bias, then SiLU:
       c_t = silu(sum_{j=0..3} w_j x_{t-3+j}), zeros before the sequence
    3. beta = sigmoid(b);  g = -exp(A_log) * softplus(a + dt_bias), float32,
       one of each a value head and token (`A_log` drawn as
       log(uniform(0, 16)), `dt_bias` ones)
    4. q, k L2-normed over their 128 entries (x / sqrt(sum x^2 + 1e-6)),
       q times 128 ** -0.5; each key head serves 2 value heads (repeated,
       neighbours together)
    5. per value head, S_0 = 0 in [128, 128], float32:
       S_t = exp(g_t) S_{t-1} - beta_t (exp(g_t) S_{t-1} k_t - v_t) k_t^T,
       o_t = S_t q_t
       (the same as S_t = exp(g_t) S_{t-1} (I - beta_t k_t k_t^T)
       + beta_t v_t k_t^T), computed in chunks of 64 with the state carried
       between chunks (`delta.chunked_rule`); token by token only in the
       CPU tests and the benchmark's reference
    6. y = rmsnorm_128(o) * w_n * silu(z) per head (`w_n` ones, NOT
       zero-centred, float32), then W_o (4096 -> 2048)

`full_attention` (`blocks/attention.Attention`; 16 query and 2 key/value
heads of 256): q, k, v and a gate from four products (the published `q_proj`
holds q and the gate in one matrix, per head q then gate: the same products);
a zero-centred RMSNorm over each q and k head; rotary positions on the FIRST
64 entries of each head, pairs (i, i + 32), theta 1e7; causal softmax
attention at 256 ** -0.5; `out * sigmoid(gate)` entry by entry; W_o.

`moe` (`blocks/experts.Experts`): float32 softmax over `x W_r` (2048 ->
512), the 10 largest, their weights divided by their sum (`norm_topk_prob`),
experts `W_2 (silu(W_1 x) * W_3 x)` of width 512, plus `sigmoid(x w_g) *
shared(x)` with `shared` the same form at width 512 and `w_g` of 2048. No
bias on the selection, no scale. The auxiliary balance loss is NOT built,
nor the multi-token-prediction layer (the published config has no key for
its shape).

After the last layer a zero-centred norm and the head. `layer_types` names
the layers held here, in order (a pipeline stage holds some of the published
48). One chip's share, both mixers, the experts' layer, the norms, the head
and rematerialisation are `models/blocks/`'s, imported.

Device scopes: `linear_attn` with `gdn_in_proj`, `gdn_conv`, `gdn_rule`,
`gdn_norm_gate`, `gdn_out_proj` inside it (`blocks/delta.py`); `attn_full`,
`attn_proj` (with `qk_norm`, `rope`, `attn_gate` inside it); `moe_router`,
`moe_experts`, `moe_shared` (the shared expert's gate inside it); `lm_head`,
`embed`, `rms_norm`. Counters as `blocks/experts.py`'s with
`moe_shared_gate_mean`, and the layers' means `attn_gate_mean`,
`gdn_decay_mean`, `gdn_beta_mean`, `gdn_state_rms`.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from .blocks.attention import FULL, Attention, recomputed
from .blocks.common import RMSNorm, own_fields, untied_head
from .blocks.delta import GatedDeltaNet
from .blocks.experts import Experts, model_counters
from .blocks.rope import rope_inv_freq

LINEAR = "linear_attention"
PERIOD = (LINEAR, LINEAR, LINEAR, FULL)     # `full_attention_interval` 4


def _norm(m, name: str):
    return RMSNorm(m.rms_norm_eps, m.dtype, zero_centred=True, name=name)


class Mixed(nn.Module):
    """`x + mixer(norm_1(x))`, and the mixer's counters."""
    m: Any                          # the model's own fields, as a namespace
    kind: str                       # LINEAR or FULL

    @nn.compact
    def __call__(self, x):
        m = self.m
        h = _norm(m, "input_norm")(x)
        if self.kind == LINEAR:
            a, counters = GatedDeltaNet(
                m.linear_num_key_heads, m.linear_num_value_heads,
                m.linear_key_head_dim, m.linear_value_head_dim,
                m.linear_conv_kernel_dim, m.rms_norm_eps, m.dtype,
                kernels=m.kernels, name="linear_attn")(h)
        else:
            turned = int(m.head_dim * m.partial_rotary_factor)
            a, gate_mean = Attention(
                m.num_heads, m.num_kv_heads, m.head_dim, None,
                tuple(rope_inv_freq(turned, m.rope_theta).tolist()), 1.0,
                m.kernels, m.dtype, qk_norm=True,
                qk_norm_eps=m.rms_norm_eps, qk_norm_zero_centred=True,
                rope_lead=True, gate=True, name="attn")(h)
            counters = {"attn_gate_mean": gate_mean}
        return x + a, counters


class Routed(nn.Module):
    """`h + moe(norm_2(h))`, and the experts' counters."""
    m: Any

    @nn.compact
    def __call__(self, x):
        m = self.m
        f, counters = Experts(
            m.num_experts, m.experts_per_token, m.expert_width,
            m.expert_share, m.expert_shares, m.dtype, scoring="softmax",
            shared_width=m.shared_expert_width, shared_gate=True,
            kernels=m.kernels, name="moe")(_norm(m, "post_attn_norm")(x))
        return x + f, counters


class Layer(nn.Module):
    """A layer in two halves, each recomputed in the backward pass on its
    own (`mixer/...` and `routed/...` in the layer's parameters): while the
    experts' half is differentiated (5.2 GB in its fall-back with room for
    every one of a token's 10 assignments) nothing of the mixer's is kept
    (2.5 GB), and the other way round. As one recomputed unit the step's
    temporaries came to 12.2 GB device-less for a v5e beside 5.1 at rest
    (PERF.md section 6, PR 44)."""
    m: Any
    kind: str

    @nn.compact
    def __call__(self, x):
        x, mixer = recomputed(Mixed)(self.m, self.kind, name="mixer")(x)
        x, counters = recomputed(Routed)(self.m, name="routed")(x)
        return x, (counters, mixer)


class Qwen3Next(nn.Module):
    vocab_size: int = 151936        # embedding and head rows held here
    hidden_size: int = 2048
    num_layers: int = 48
    layer_types: Optional[Tuple[str, ...]] = None   # None: the published
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    num_experts: int = 512          # the router's width, never cut
    experts_per_token: int = 10
    expert_width: int = 512
    shared_expert_width: int = 512
    expert_share: int = 0           # which share of the experts is held,
    expert_shares: int = 1          # of how many
    rms_norm_eps: float = 1e-6
    kernels: Optional[bool] = None  # None: where the backend is a TPU
    dtype: Any = jnp.float32

    def init(self, rngs, *args, **kwargs):
        """`nn.Module.init` with every key given as one of the generator the
        chip has in hardware (`rbg`), so that every leaf is drawn by it: this
        model's init program is mostly normal draws, and the TPU compiler is
        done with it in 7.1 s where the default generator's takes it 15.5
        (device-less for a v5e; on the chip 15.3 s for 29.7 of a run from an
        empty cache, PERF.md section 6, PR 44). The draw's values are the
        backend's own; nothing reads them (the benchmark's weights are its
        reference's)."""
        def hardware(key):
            if not jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
                key = jax.random.wrap_key_data(key)
            words = jax.random.key_data(key).reshape(-1)[:2]
            return jax.random.wrap_key_data(jnp.tile(words, 2), impl="rbg")
        rngs = ({name: hardware(key) for name, key in rngs.items()}
                if isinstance(rngs, dict) else hardware(rngs))
        return super().init(rngs, *args, **kwargs)

    @nn.compact
    def __call__(self, tokens, train: bool = True,
                 return_counters: bool = False):
        # tokens int32 [B, S] -> logits float32 [B, S, vocab_size]
        kinds = tuple(self.layer_types or PERIOD * (self.num_layers // 4 + 1)
                      )[:self.num_layers]
        if len(kinds) != self.num_layers or set(kinds) - {LINEAR, FULL}:
            raise ValueError(f"{self.num_layers} layers, layer_types "
                             f"{self.layer_types}")
        # unit embeddings, as `Mellum2`'s: the stream has to carry the token
        with jax.named_scope("embed"):
            x = nn.Embed(self.vocab_size, self.hidden_size, dtype=self.dtype,
                         embedding_init=nn.initializers.normal(1.0),
                         name="embed")(tokens)
        widths = own_fields(self)
        per_layer, mixers = [], {}
        for i, kind in enumerate(kinds):
            x, (counters, mixer) = Layer(widths, kind, name=f"layers_{i}")(x)
            per_layer.append(counters)
            for name, value in mixer.items():
                mixers.setdefault(name, []).append(value)
        x = RMSNorm(self.rms_norm_eps, self.dtype, zero_centred=True,
                    name="norm")(x)
        logits = untied_head(self, x)
        if not return_counters:
            return logits
        return logits, {
            **model_counters(per_layer),
            **{name: jnp.mean(jnp.stack(values))
               for name, values in mixers.items()}}
