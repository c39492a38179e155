"""JoyAI-LLM-Flash (jdopensource/JoyAI-LLM-Flash, config.json, `model_type`
`joyai_llm_flash`, 48B-A2.7B): a decoder-only language model with latent
attention (MLA), one leading dense layer, then layers of 256 sparse experts
chosen by a sigmoid router with a selection bias, a shared expert beside
them, and a multi-token-prediction module. Defaults are the published
widths: hidden 2048, 32 heads, queries through a rank-1536 bottleneck, keys
and values through a rank-512 latent, query/key heads of 128 + 64 (the 64
rotary) against value heads of 128, experts of 768 with 8 a token times 2.5,
a dense layer of 7168, 40 layers.

Per layer, pre-norm (RMSNorm, eps 1e-6, no biases; `h` a token's stream):

    cq = norm(n1(h) Wqa);  [qn | qr] = cq Wqb                 per head
    [ckv | kr] = n1(h) Wkva;  ckv = norm(ckv);  [kn | v] = ckv Wkvb
    q_i = [qn_i | rope(qr_i)],  k_i = [kn_i | rope(kr)]   (kr one for all
          heads; rope turns the ADJACENT pairs (2j, 2j + 1) in place,
          `rope_interleave`; transformers de-interleaves both q and k first
          and turns the halves, which gives the same q k^T)
    h' = h + concat_i(softmax_causal(q_i k_i^T / sqrt(192)) v_i) Wo
    s = sigmoid(n2(h') Wr) in float32;  C = top8(s + b);
    y = h' + sum_{e in C} (2.5 s_e / sum_C s) F_e(n2(h')) + F_shared(n2(h'))
    layer 0:  y = h' + F_dense(n2(h')),     F = W2 (silu(W1 x) * W3 x)

`b` (`router_bias`) is a leaf of `params`, zero at init; it only selects, so
its gradient is exactly zero. The rule that moves it by the experts' load
(`topk_method` `noaux_tc`) is NOT built: the config gives no speed for it.

The multi-token-prediction module (`num_nextn_predict_layers` 1), given the
next tokens: `u_t = Wm [norm_e(embed(x_{t+1})) | norm_h(z_t)]` (z the last
layer's output before the final norm), one more attention-and-experts
block, the module's own final norm and the SHARED head; its logits at t
predict x_{t+2}. It runs over all S positions (the loss leaves the last
out: attention is causal and the experts go token by token, so position
S - 1 reaches no other). Embedding and head get gradient by both paths.

The leading dense layers are `layers_0`, ...; the layers with experts are
equal, so they are ONE body run as a loop of the program (`nn.scan`) over
weights stacked along a leading axis, `expert_layers/...` `[layers, ...]`:
the step program compiles one such layer, not one a layer (PERF.md section
6, PR 33: what that saves and what the stacked gradient costs in memory).

One chip's share, the experts' layer, its grouped products without dropped
tokens, the attention paths (splash attention on a TPU, blocks of queries
elsewhere), RMSNorm, the rotary helper, the head and rematerialisation are
`models/blocks/`'s, imported.

Device scopes: `attn_mla` (softmax(q k^T) v and its backward), `mla_proj`
(the five products, the two inner norms, the rotary) and inside it `mla_q`,
`mla_kv`, `mla_out` (the products and norms of each path) and `mla_assemble`
(q and k put together, `rope.apply_rope`'s `rope` inside it: one fused
pass over the whole 192-wide q, whose first 128 lanes pass through, with the
scale and the one rounding, and one over the shared key part, against one
set of angles that `rope.rope_table` lays out once on the host, at q's
192 lanes and at the key part's 64),
`moe_router`, `moe_experts` (with `blocks/experts.py`'s scopes inside both),
`moe_shared`, `dense_mlp`, `lm_head`, `mtp`, `embed`, `rms_norm` from
`common.RMSNorm`, and `layer_scan` around the scanned layers (what lies
directly under it is the loop's own slicing and stacking). Counters as
`blocks/experts.py`'s: `moe_held_assignments`, `moe_room_used`,
`moe_load_max_over_mean`, `moe_tokens_unserved`.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from .blocks.attention import plain_attention, recomputed, splash_attention
from .blocks.common import INIT, RMSNorm, own_fields, use_kernels
from .blocks.experts import Experts, GatedMLP, model_counters
from .blocks.rope import apply_rope, rope_inv_freq


class LatentAttention(nn.Module):
    m: Any                          # the model's own fields, as a namespace

    @nn.compact
    def __call__(self, x):
        m = self.m
        b, s, hidden = x.shape
        heads, rank = m.num_heads, m.kv_lora_rank
        nope, rot, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim

        def proj(name, features, axis=-1):
            return nn.DenseGeneral(features, axis=axis, use_bias=False,
                                   dtype=m.dtype, kernel_init=INIT,
                                   name=name)

        with jax.named_scope("mla_proj"):
            with jax.named_scope("mla_q"):
                cq = RMSNorm(m.rms_norm_eps, m.dtype, name="q_a_norm")(
                    proj("q_a_proj", m.q_lora_rank)(x))
                q = proj("q_b_proj", (heads, nope + rot))(cq)
            with jax.named_scope("mla_kv"):
                kva = proj("kv_a_proj", rank + rot)(x)
                ckv = RMSNorm(m.rms_norm_eps, m.dtype, name="kv_a_norm")(
                    kva[..., :rank])
                kv = proj("kv_b_proj", (heads, nope + dv))(ckv)
            # q and k put together, the rotary turn (`rope`) inside it:
            # q whole in one pass (its first `nope` lanes pass through)
            with jax.named_scope("mla_assemble"):
                inv_freq = rope_inv_freq(rot, m.rope_theta)
                q = apply_rope(q, inv_freq, interleave=m.rope_interleave,
                               out_scale=(nope + rot) ** -0.5,
                               dtype=m.dtype).reshape(
                                   b, s, heads, 1, nope + rot)
                k_rot = apply_rope(kva[:, :, None, rank:], inv_freq,
                                   interleave=m.rope_interleave,
                                   dtype=m.dtype)
                k = jnp.concatenate(
                    [kv[..., :nope],
                     jnp.broadcast_to(k_rot, (b, s, heads, rot))], axis=-1)
                v = kv[..., nope:]
        with jax.named_scope("attn_mla"):
            if use_kernels(m.kernels):
                out = splash_attention(q, k, v, None)
            else:
                out = plain_attention(q, k, v, None)
        with jax.named_scope("mla_proj"), jax.named_scope("mla_out"):
            return proj("o_proj", hidden, axis=(-2, -1))(
                out.reshape(b, s, heads, dv))


class Layer(nn.Module):
    m: Any
    dense: bool                     # a leading layer without experts

    @nn.compact
    def __call__(self, x):
        m = self.m
        h = RMSNorm(m.rms_norm_eps, m.dtype, name="input_norm")(x)
        x = x + LatentAttention(m, name="attn")(h)
        h = RMSNorm(m.rms_norm_eps, m.dtype, name="post_attn_norm")(x)
        if self.dense:
            return x + GatedMLP(m.dense_width, "dense_mlp", name="mlp")(h), {}
        y, counters = Experts(
            m.num_experts, m.experts_per_token, m.expert_width,
            m.expert_share, m.expert_shares, m.dtype, scoring="sigmoid",
            select_bias=True, scale=m.routed_scaling_factor,
            shared_width=m.shared_experts * m.expert_width,
            kernels=m.kernels, name="moe")(h)
        return x + y, counters


class JoyAIFlash(nn.Module):
    vocab_size: int = 129280        # embedding and head rows held here
    hidden_size: int = 2048
    num_layers: int = 40
    first_k_dense_replace: int = 1  # leading layers with a dense MLP
    dense_width: int = 7168
    num_heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 32000000.0
    rope_interleave: bool = True
    num_experts: int = 256          # the router's width, never cut
    experts_per_token: int = 8
    expert_width: int = 768
    shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    expert_share: int = 0           # which share of the experts is held,
    expert_shares: int = 1          # of how many
    rms_norm_eps: float = 1e-6
    num_nextn_predict_layers: int = 1
    kernels: Optional[bool] = None  # None: where the backend is a TPU
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, tokens, train: bool = True,
                 return_counters: bool = False, next_tokens=None):
        """tokens int32 [B, S] -> logits float32 [B, S, vocab_size]; with
        `next_tokens` (tokens shifted by one) and the module built,
        (logits, the module's logits)."""
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError(f"{self.num_nextn_predict_layers} prediction "
                             f"modules: one is built, or none")
        # unit embeddings, as `Mellum2`'s: the stream has to carry the token
        embed = nn.Embed(self.vocab_size, self.hidden_size, dtype=self.dtype,
                         embedding_init=nn.initializers.normal(1.0),
                         name="embed")
        layer = recomputed(Layer)
        widths = own_fields(self)
        head = self.param("lm_head", INIT,
                          (self.hidden_size, self.vocab_size), jnp.float32)

        def logits_of(x):
            with jax.named_scope("lm_head"):
                return jnp.dot(x, head.astype(self.dtype),
                               preferred_element_type=jnp.float32)

        with jax.named_scope("embed"):
            x = embed(tokens)
        per_layer = []
        dense = min(self.first_k_dense_replace, self.num_layers)
        for i in range(dense):
            x, _ = layer(widths, True, name=f"layers_{i}")(x)
        if self.num_layers > dense:
            # the expert layers are equal: ONE body, run as a loop of the
            # program over weights stacked along a leading axis (a layer
            # compiles once, not once a layer). Directly under the scope
            # `layer_scan` is what the loop itself costs: the slices of the
            # stacked weights, the writes into the stacked gradient
            with jax.named_scope("layer_scan"):
                x, counters = nn.scan(
                    layer, variable_axes={"params": 0},
                    split_rngs={"params": True},
                    length=self.num_layers - dense)(
                        widths, False, name="expert_layers")(x)
            per_layer += [jax.tree.map(lambda v, i=i: v[i], counters)
                          for i in range(self.num_layers - dense)]
        out = logits_of(RMSNorm(self.rms_norm_eps, self.dtype,
                                name="norm")(x))
        if self.num_nextn_predict_layers and (
                next_tokens is not None or self.is_initializing()):
            ahead = tokens if next_tokens is None else next_tokens
            with jax.named_scope("mtp"):
                both = jnp.concatenate([
                    RMSNorm(self.rms_norm_eps, self.dtype,
                            name="mtp_embed_norm")(embed(ahead)),
                    RMSNorm(self.rms_norm_eps, self.dtype,
                            name="mtp_hidden_norm")(x)], axis=-1)
                u = nn.Dense(self.hidden_size, use_bias=False,
                             dtype=self.dtype, kernel_init=INIT,
                             name="mtp_proj")(both)
                u, counters = layer(widths, False, name="mtp_block")(u)
                per_layer.append(counters)
                out = (out, logits_of(RMSNorm(
                    self.rms_norm_eps, self.dtype, name="mtp_norm")(u)))
        if not return_counters:
            return out
        return out, model_counters(per_layer)
