"""Nemotron-H (`model_type` `nemotron_h`; Nemotron-Labs-TwoTower-30B-A3B's
CAUSAL tower, nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16, config.json;
NVIDIA, "Nemotron-H", arXiv:2504.03624): a decoder-only language model
whose blocks are ONE module each under one norm, by a pattern string: `M` a
Mamba-2 state-space mixer, `E` sparse experts, `*` attention. Defaults are
the published widths: hidden 2688; 64 state-space heads of 64 with a state
of 128, `B` and `C` in 8 groups, a convolution of 4 taps with a bias, chunks
of 128; 32 query and 2 key/value heads of 128 under no positions; 128
squared-ReLU experts of 1856, six a token, beside a shared one of 3712; 52
blocks; an untied head over 131 072 rows.

The published model's second, denoising tower (adaLN, attention both ways
inside a block of tokens, conditioning across the towers) and its diffusion
objective are NOT built: the catalogued config has no key of theirs, and
this system trains one objective, the next token's loss.

The equations; `u = norm(x)` a normed token (plain RMSNorm, scale from one,
eps 1e-5), no bias but the convolution's:

    block i:  x <- x + module_i(norm_i(x)),   module_i by `pattern[i]`

`M` (`blocks/ssm.py`, from transformers' `Mamba2Mixer.torch_forward` and
`Zamba2RMSNormGated`; 64 heads of 64: inner width 4096, NOT `expand` x
hidden):

    1. [z | xBC | dt] = W_in u      (2688 -> 4096 + 6144 + 64)
    2. xBC <- silu(conv4(xBC) + bias), depthwise and causal, zeros before
       the sequence;  [x | B | C] = xBC  (4096 | 8 x 128 | 8 x 128)
    3. dt = softplus(dt + dt_bias), no clamp (`time_step_limit` (0, inf));
       A = -exp(A_log), one a head
    4. per head h of group h // 8, S_0 = 0 in [128, 64], float32:
       S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T,   y_t = S_t^T C_t + D x_t
       in chunks of 128 (`ssm.chunked_scan`); token by token only in the
       CPU tests and the benchmark's reference
    5. v = y silu(z);  out = W_out (v / rms_512(v) * w_n), the root mean
       square over each group's 512 columns (gate first, norm second)

`E` (`blocks/experts.Experts`, the router DeepSeek-V3's, which
`models/joyai_flash.py` runs): `s = sigmoid(u W_r)` in float32 over 128;
the six largest of `s + bias` (the bias only selects; one group, no group
limit); their `s` divided by their sum, times 2.5; the held experts' `W_down
relu(W_up u)^2` weighted so, plus the shared expert of 3712 in the same
form, no gate on it.

`*` (`blocks/attention.Attention(positions=False)`): q 2688 -> 32 x 128, k
and v -> 2 x 128, causal softmax at 128 ** -0.5, q and k NOT turned (the
family has no position embeddings), o 4096 -> 2688.

After the last block a norm and the head. `pattern` names the blocks held
here, in order (a pipeline stage holds some of the published 52). Each block
is recomputed whole in its backward pass: a block IS a half of the sibling
models' layers. One chip's share, the mixers, the experts' layer, the norms,
the head and rematerialisation are `models/blocks/`'s, imported.

At init (the family's `initializer_range` and `rescale_prenorm_residual`):
normal(0, 0.02) products, those that WRITE the stream over the square root
of the published depth (`OUT_INIT_SCALE`; the scheme is GPT-2's, "the
weights of residual layers", and the family's own trainer gives every output
layer the scaled deviation: at 0.02 the shared expert's `relu(.)^2`, whose
mean is one vector for all tokens, is two thirds of the stream behind the
first `E` block and the next routers choose by it), a unit embedding,
Mamba-2's own draws for the taps, `A_log`, `dt_bias` and `D` (`blocks/ssm.py`).

Device scopes: `ssm` with `ssm_in_proj`, `ssm_conv`, `ssm_scan`,
`ssm_norm_gate`, `ssm_out_proj` inside it (`blocks/ssm.py`); `attn_full`,
`attn_proj`; `moe_router`, `moe_experts`, `moe_shared`; `lm_head`, `embed`,
`rms_norm`. Counters as `blocks/experts.py`'s, and the state-space blocks'
means `ssm_dt_mean`, `ssm_decay_mean`, `ssm_state_rms`.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from .blocks.attention import Attention, recomputed
from .blocks.common import RMSNorm, own_fields, untied_head
from .blocks.experts import RELU2, Experts, model_counters
from .blocks.ssm import Mamba2Mixer

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
PUBLISHED = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
# `rescale_prenorm_residual`: every product that writes the residual stream
# (a mixer's `out_proj`, the experts' and the shared expert's `w2`, the
# attention's `o_proj`) starts at normal(0, 0.02) over the square root of the
# PUBLISHED depth, however many of its blocks are held here
OUT_INIT_SCALE = len(PUBLISHED) ** -0.5


class Block(nn.Module):
    """`x + module(norm(x))`, and the module's counters."""
    m: Any                          # the model's own fields, as a namespace
    kind: str                       # MAMBA, EXPERTS or ATTENTION

    @nn.compact
    def __call__(self, x):
        m = self.m
        h = RMSNorm(m.rms_norm_eps, m.dtype, name="norm")(x)
        if self.kind == MAMBA:
            y, counters = Mamba2Mixer(
                m.mamba_num_heads, m.mamba_head_dim, m.ssm_state_size,
                m.n_groups, m.conv_kernel, m.rms_norm_eps, m.dtype,
                m.chunk_size, OUT_INIT_SCALE, m.kernels, name="mixer")(h)
        elif self.kind == EXPERTS:
            y, counters = Experts(
                m.num_experts, m.experts_per_token, m.expert_width,
                m.expert_share, m.expert_shares, m.dtype, scoring="sigmoid",
                select_bias=True, scale=m.route_scale,
                shared_width=m.shared_expert_width, kernels=m.kernels,
                form=RELU2, out_init_scale=OUT_INIT_SCALE, name="moe")(h)
        else:
            y, counters = Attention(
                m.num_heads, m.num_kv_heads, m.head_dim, None, (), 1.0,
                m.kernels, m.dtype, positions=False,
                out_init_scale=OUT_INIT_SCALE, name="attn")(h), {}
        return x + y, counters


class NemotronH(nn.Module):
    vocab_size: int = 131072        # embedding and head rows held here
    hidden_size: int = 2688
    pattern: str = PUBLISHED        # the blocks held here, a letter each
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    num_experts: int = 128          # the router's width, never cut
    experts_per_token: int = 6
    expert_width: int = 1856
    shared_expert_width: int = 3712
    route_scale: float = 2.5
    expert_share: int = 0           # which share of the experts is held,
    expert_shares: int = 1          # of how many
    rms_norm_eps: float = 1e-5
    kernels: Optional[bool] = None  # None: where the backend is a TPU
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, tokens, train: bool = True,
                 return_counters: bool = False):
        # tokens int32 [B, S] -> logits float32 [B, S, vocab_size]
        if not self.pattern or set(self.pattern) - {MAMBA, EXPERTS,
                                                    ATTENTION}:
            raise ValueError(f"pattern {self.pattern!r}")
        # unit embeddings, as `Mellum2`'s: the stream has to carry the token
        with jax.named_scope("embed"):
            x = nn.Embed(self.vocab_size, self.hidden_size, dtype=self.dtype,
                         embedding_init=nn.initializers.normal(1.0),
                         name="embed")(tokens)
        block = recomputed(Block)
        widths = own_fields(self)
        routed, mixers = [], {}
        for i, kind in enumerate(self.pattern):
            x, counters = block(widths, kind, name=f"blocks_{i}")(x)
            if kind == EXPERTS:
                routed.append(counters)
            elif kind == MAMBA:
                for name, value in counters.items():
                    mixers.setdefault(name, []).append(value)
        x = RMSNorm(self.rms_norm_eps, self.dtype, name="norm")(x)
        logits = untied_head(self, x)
        if not return_counters:
            return logits
        return logits, {
            **(model_counters(routed) if routed else {}),
            **{name: jnp.mean(jnp.stack(values))
               for name, values in mixers.items()}}
