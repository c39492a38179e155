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

**One chip's share of an expert group.** The layer is told which experts it
holds, `(expert_share, expert_shares)`: experts `share * E / shares` up to
the next share's first. It routes over all E and adds only its own experts'
terms; what the absent experts would add is left out and the partial result
goes on (on one chip there is no exchange, and nothing stands in for one).
`vocab_size` is the number of embedding and head rows held: a sliced
vocabulary is a smaller vocabulary.

No token is dropped: the (token, expert) assignments are sorted by held
expert, each one's row gathered, and the experts' three products run as
grouped products over the rows each expert got (`grouped_product`). With
`kernels` (the default on a TPU) a product is a Pallas kernel of
`ops/grouped_matmul.py` on tiles computed from its shape: an expert's whole
matrix as one block, which stays in VMEM for all of the expert's row tiles,
and only the row tiles that hold rows visited; elsewhere (the CPU tests),
and for a room that no row tile divides, `lax.ragged_dot` (which the TPU
compiler serves with a kernel of its own that takes no tiles from its
caller: 23 % of the matrix unit at 2304 x 896 where the tiled kernel reads
70 %, `PERF.md` section 6, PR 41). Shapes are static, so there is room for
twice an even load's rows where the step sees that they suffice and for
every assignment (all of a token's experts held) where not: a `lax.cond`,
not a capacity.

Attention never builds `[S, S]`. With `kernels` (the default on a TPU) it is
the Pallas splash-attention kernel of `jax.experimental`, which skips the
blocks a mask leaves empty, so a window layer's work goes with S x window,
on tiles and in the form that `splash_sizes` computes from the call's shape
(a full layer's backward pass is ONE kernel call, a window layer's two);
elsewhere (the CPU tests) blocks of queries against the keys their mask can
reach.

Not in this model's published config and so not used by it: an auxiliary
load-balance loss; nor, though the pieces below offer them to the models
that share them: a shared expert, sigmoid scores with a selection bias, a
scale and a constant in the weights' sum (`Experts`, `GatedMLP`),
adjacent-pair rotary (`apply_rope`), values of a head size of their own and
a key/value head for every query head (`plain_attention`,
`splash_attention`), a norm over each q and k head before the turn
(`Attention(qk_norm=True)`, under the scope `qk_norm` inside `attn_proj`),
a layer without positions (`Attention(positions=False)`: q and k are not
turned, only scaled, and the layer opens no `rope` scope), a sigmoid gate
on the attention's output (`Attention(gate=True)`, `gated_output`, under
the scope `attn_gate` inside `attn_proj`); the benchmark's configuration
file lists what is assumed under `assumed`. Which model sets which field:

    field                              mellum2  joyai_flash  lfm2_moe  afmoe
    Attention  qk_norm                 -        (own MLA)    yes       yes
               positions=False         -        (own MLA)    -         full layers
               gate                    -        (own MLA)    -         yes
    Experts    scoring                 softmax  sigmoid      sigmoid   sigmoid
               select_bias             -        yes          yes       yes
               scale                   1        2.5          1         2.826
               sum_eps                 0        0            1e-6      1e-20
               shared_width            0        768          0         1024
    GatedMLP   leading dense layers    -        1            2         2

Device scopes (`jax.named_scope`; `benchmarks/model_scopes.py` reads the
first five, `benchmarks/scope_tree.py` the whole path): `attn_window`,
`attn_full`, `moe_router`, `moe_experts`, `lm_head`; inside `moe_experts`
`moe_to_rows`, `moe_to_tokens`, `moe_gate`, `moe_product_glue` and, around
the products' kernels `grouped_fwd`, `grouped_dx`, `grouped_dw` and their
schedule, `moe_product` (a name no reader lists: its time is `moe_experts`'
own); inside
`moe_router` `moe_route_sort`; `attn_proj` (the four projections, not around
attention proper) with `rope` inside it; `rms_norm` (every instance);
`embed`. `rope` holds the rotary turn whole: one fused pass over q and one
over k (the pairs' exchange by a 0/1 product, the turn in float32, the
attention's scale, the one rounding) against cos and sin tables that
`rope_table` makes once on the host, two for this model (default and yarn).
A new scope goes INSIDE the one a metric reads (docs/OBSERVABILITY.md,
"Device scopes").
Counters (returned with `return_counters=True`, logged through the loss
function's auxiliary output): `moe_held_assignments`, `moe_room_used`,
`moe_load_max_over_mean`, `moe_tokens_unserved`; a model with gated
attention adds `attn_gate_mean` (`models/afmoe.py`).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import types
from typing import Any, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops import grouped_matmul

SLIDING, FULL = "sliding_attention", "full_attention"
_PERIOD = (SLIDING, SLIDING, SLIDING, FULL)


def _INIT(key, shape, dtype=jnp.float32):
    """normal(0, 0.02), drawn flat and folded to `shape`: entry for entry
    the draw of `shape` itself (the generator counts entries, not rows),
    from a program the TPU compiler is done with in 0.7 s where a draw of
    three axes takes it 3.3 (`[2048, 32, 128]`) to 9.3 (`[8, 2048, 1024]`);
    a model's init program is mostly such draws."""
    return nn.initializers.normal(0.02)(key, (math.prod(shape),),
                                        dtype).reshape(shape)


# query rows a block of the plain attention path takes at a time
_PLAIN_BLOCK = 128
# splash attention's compute tile on a v5e (queries x keys, forward and
# backward): what one pass of the softmax's vector work covers
_SPLASH_BLOCK = 512
# The fused backward kernel hands dq out as one bfloat16 partial sum for each
# memory block of keys: at most this many (each is rounded before their sum)
_DQ_PARTS = 4
# and no more bytes of them than this, a sixteenth of a v5e's 16 GiB
_DQ_PARTS_BYTES = 2 ** 30
# compute tiles of keys to a memory block of a full layer's forward kernel
_KV_TILES = 4
# What a recomputed layer keeps from its first forward pass: the attention
# kernel's output and row statistics (0.4 GB a layer at the benchmark's
# size), so that kernel does not run a second time. The experts' products do
# (their backward pass keeps more than their output).
_SAVED = "attn_kernel_out"
# the slots that a block of tokens has for its live rows in `_summed`
_ROOM = 512


# ------------------------------------------------------------------ rotary

def rope_inv_freq(head_dim: int, theta: float) -> np.ndarray:
    """`rope_type` default: theta ** (-2i / d) for each of the d / 2 pairs."""
    return 1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                           / head_dim)


def yarn_inv_freq(head_dim: int, theta: float, factor: float,
                  original_max: int, beta_fast: float, beta_slow: float,
                  truncate: bool = True) -> np.ndarray:
    """`rope_type` yarn (Peng et al., arXiv:2309.00071, as transformers'
    `_compute_yarn_parameters`): pairs that turn more than `beta_fast`
    times within the original context keep their frequency, those that turn
    less than `beta_slow` times are slowed by `factor`, a linear ramp over
    the pair index between."""
    def pair_of(turns):
        return (head_dim * math.log(original_max / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low, high = pair_of(beta_fast), pair_of(beta_slow)
    if truncate:
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    plain = rope_inv_freq(head_dim, theta)
    return plain / factor * ramp + plain * (1.0 - ramp)


@functools.lru_cache(maxsize=None)
def rope_table(positions: int, inv_freq: Tuple[float, ...], scale: float,
               interleave: bool, width: int):
    """cos and signed sin times `scale`, float32 [positions, width], laid
    out at the width of the axis they turn: made ONCE for each (positions,
    frequencies, scale, layout) a process meets, on the host, and constants
    of every program that uses them (never computed on the device, so never
    inside a fusion that visits every head). The angle is float32 position
    times float32 frequency. Half-split `[cos | cos]` and `[-sin | sin]`;
    adjacent pairs each value twice, the sine's sign alternating; 1 and 0
    in the lanes before the turned part."""
    ang = (np.arange(positions, dtype=np.float32)[:, None]
           * np.asarray(inv_freq, np.float32)[None, :]).astype(np.float64)
    cos, sin = np.cos(ang) * scale, np.sin(ang) * scale
    if interleave:
        cos = np.repeat(cos, 2, axis=-1)
        sin = np.stack([-sin, sin], axis=-1).reshape(positions, -1)
    else:
        cos, sin = np.tile(cos, 2), np.concatenate([-sin, sin], axis=-1)
    still = ((0, 0), (width - cos.shape[-1], 0))
    return (np.pad(cos, still, constant_values=1.0).astype(np.float32),
            np.pad(sin, still).astype(np.float32))


def _partner_matrix(width: int, rot: int, interleave: bool) -> np.ndarray:
    """0/1 [width, width]: `x @ m` holds at every lane of the turned part
    (the last `rot`) the other entry of that lane's pair, and 0 before it."""
    place = np.arange(rot)
    other = place ^ 1 if interleave else (place + rot // 2) % rot
    m = np.zeros((width, width), np.float32)
    m[width - rot + other, width - rot + place] = 1.0
    return m


class _Turn(NamedTuple):
    """How `_turned` turns: the width of the turned part, the pairs'
    layout, whether it turns BACK (the sine's sign: the cotangent's turn),
    the factor and dtype of what it hands out, the dtype of its cotangent."""
    rot: int
    interleave: bool
    back: bool
    out_scale: float
    dtype: Any
    cotangent_dtype: Any


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _turned(how: _Turn, x, cos, sin):
    """`(x * cos + partner(x) * sin) * out_scale` in float32, rounded once
    to `how.dtype`; the lanes before the turned part pass through (a
    `where`, no product with 0 and 1). `partner` is a product with a 0/1
    matrix: every output is ONE input times 1, exact in any dtype, and the
    compiler runs it on the matrix unit inside the fusion that reads `x`
    and writes the result, in whatever layout the consumer wants: one pass
    at full lane width, no split, stack or concatenation. The cotangent is
    the same function with the sine's sign turned, on the cotangent as it
    arrives (so the product sees the compute dtype there too)."""
    width = x.shape[-1]
    # bfloat16 times 1 is exact in one pass; anything wider needs them all
    exact = None if x.dtype == jnp.bfloat16 else lax.Precision.HIGHEST
    partner = jnp.einsum(
        "...i,ij->...j", x,
        jnp.asarray(_partner_matrix(width, how.rot, how.interleave), x.dtype),
        precision=exact, preferred_element_type=jnp.float32)
    x = x.astype(jnp.float32)
    straight = x * cos[None, :, None, :]
    across = partner * sin[None, :, None, :]
    y = straight - across if how.back else straight + across
    if width > how.rot:
        lane = lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
        y = jnp.where(lane >= width - how.rot, y, x)
    return (y * how.out_scale).astype(how.dtype)


def _turned_fwd(how, x, cos, sin):
    return _turned(how, x, cos, sin), (cos, sin)


def _turned_bwd(how, tables, g):
    back = how._replace(back=not how.back, dtype=how.cotangent_dtype,
                        cotangent_dtype=how.dtype)
    return _turned(back, g, *tables), None, None


_turned.defvjp(_turned_fwd, _turned_bwd)


def apply_rope(x, inv_freq, scale: float = 1.0, interleave: bool = False,
               out_scale: float = 1.0, dtype: Any = jnp.float32):
    """Rotate the last `2 * len(inv_freq)` entries of `x` [B, S, H, W] by
    their position (`inv_freq`: the pairs' frequencies, any sequence); what
    lies before them passes through. Pair i is (x[i],
    x[i + D/2]), or with `interleave` the adjacent (x[2i], x[2i + 1]),
    turned in place; cos and sin times `scale` (yarn's `attention_factor`).
    In float32; the result times `out_scale` (the attention's 1 / sqrt(d)),
    rounded once to `dtype`. One pass over `x` against `rope_table`'s
    constants (`_turned`)."""
    with jax.named_scope("rope"):
        cos, sin = rope_table(
            x.shape[1], tuple(np.asarray(inv_freq, np.float64).tolist()),
            float(scale), bool(interleave), x.shape[-1])
        how = _Turn(2 * len(inv_freq), bool(interleave), False,
                    float(out_scale), jnp.dtype(dtype), x.dtype)
        return _turned(how, x, cos, sin)


# --------------------------------------------------------------- attention

def allowed(q_pos, k_pos, window: Optional[int]):
    """The mask: key j is seen from query i when `0 <= i - j` and, in a
    window layer, `i - j < window`."""
    d = q_pos[:, None] - k_pos[None, :]
    ok = d >= 0
    return ok & (d < window) if window else ok


def plain_attention(q, k, v, window: Optional[int], block: int = _PLAIN_BLOCK):
    """softmax(q k^T + mask) v in blocks of queries, no kernel. q [B, S,
    Hkv, G, D] (scaled), k [B, S, Hkv, D], v [B, S, Hkv, Dv] (a head size
    of its own). A block of a window layer takes the `block + window` keys
    its mask can reach, a block of a full layer all S: scores are `[block,
    keys]`, never `[S, S]`."""
    b, s, hkv, g, d = q.shape
    block = min(block, s)
    if s % block:
        raise ValueError(f"{s} positions are no whole number of blocks of "
                         f"{block} queries")
    span = s if not window else min(
        s, -(-(window - 1) // block) * block + block)
    nblk = s // block

    def one(i):
        q_i = lax.dynamic_slice_in_dim(q, i * block, block, axis=1)
        start = jnp.clip(i * block + block - span, 0, s - span)
        k_i = lax.dynamic_slice_in_dim(k, start, span, axis=1)
        v_i = lax.dynamic_slice_in_dim(v, start, span, axis=1)
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", q_i, k_i,
                            preferred_element_type=jnp.float32)
        ok = allowed(i * block + jnp.arange(block), start + jnp.arange(span),
                     window)
        scores = jnp.where(ok[None, None, None], scores, -1e30)
        p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("bhgqk,bkhd->bqhgd", p, v_i)

    out = lax.map(jax.checkpoint(one), jnp.arange(nblk))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, hkv, g, v.shape[-1])


def splash_sizes(b: int, s: int, heads: int, d: int,
                 window: Optional[int]):
    """The kernels' `BlockSizes` from what a call shows: `b` sequences of `s`
    positions, `heads` query heads of q/k size `d`, a window or none
    (`PERF.md` section 6, PR 42, has each form's time alone on the chip).

    A window layer: the dq and the dkv kernel, every block `_SPLASH_BLOCK`
    square (or `s`, where that is less). Their grids shrink to the blocks
    the window leaves, and a wider block of keys widens the span a block
    of queries visits: no other size beat this one at windows of 1024 and
    2048 of 8192.

    A full layer: the ONE fused backward kernel (dq's product in the dkv
    kernel: the scores, the softmax's vector work and `do v^T` once, not
    twice), whose grid does not shrink, which costs a causal mask nothing.
    It hands dq out as `s // block_kv_dkv` partial sums, each rounded to
    q's dtype, so `block_kv_dkv` is the smallest memory block of up to
    `_KV_TILES` compute tiles that leaves `_DQ_PARTS` of them or fewer in
    `_DQ_PARTS_BYTES` or less (the compute tiles inside a visited memory
    block are never skipped, so a smaller block wastes less of the
    diagonal; a larger one does not fit the kernel's VMEM at every head
    size); where there is none (64 k positions), the window layer's form.
    The forward kernel's memory block is `_KV_TILES` compute tiles of keys
    too: a grid step's own cost is then paid a quarter as often (13-18 %
    of the kernel at heads of 64 to 192). A head wider than 128 lanes
    (192) takes two lane tiles in every block the kernel holds in VMEM,
    and the fused kernel over 512 x 512 tiles then asks for 16.07 of the
    16 MiB it may have: such a head computes on tiles of half as many
    keys, which costs 1-2 %."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel)
    blk = min(_SPLASH_BLOCK, s)
    wide = [m for m in range(blk, _KV_TILES * blk + 1, blk) if s % m == 0]
    fused = [] if window else [
        m for m in wide if s // m <= _DQ_PARTS
        and b * (s // m) * heads * s * d * 2 <= _DQ_PARTS_BYTES]
    if not fused:
        return kernel.BlockSizes(
            block_q=blk, block_kv=blk, block_kv_compute=blk, block_q_dkv=blk,
            block_kv_dkv=blk, block_kv_dkv_compute=blk, block_q_dq=blk,
            block_kv_dq=blk)
    return kernel.BlockSizes(
        block_q=blk, block_kv=wide[-1], block_kv_compute=blk,
        block_q_dkv=blk, block_kv_dkv=fused[0],
        block_kv_dkv_compute=(blk // 2 if d > 128 and blk == _SPLASH_BLOCK
                              else blk),
        use_fused_bwd_kernel=True)


def splash_attention(q, k, v, window: Optional[int]):
    """The same by the Pallas splash-attention kernel, forward and backward;
    the blocks a mask leaves empty are never visited. Where a key/value
    head serves G > 1 query heads: one call a sequence and key/value head,
    its G query heads against the one key/value head (`make_splash_mqa`).
    Where every query head has a key/value head of its own (G = 1): one
    call a sequence over all heads (`make_splash_mha`). The kernel takes
    the values' head size from `v`, its tiles and the form of its backward
    pass from `splash_sizes`."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel, splash_attention_mask as masks)
    b, s, hkv, g, d = q.shape
    mask = (masks.LocalMask((s, s), (window - 1, 0), 0) if window
            else masks.CausalMask((s, s)))
    sizes = splash_sizes(b, s, hkv * g, d, window)
    if g == 1:
        call = kernel.make_splash_mha_single_device(
            masks.MultiHeadMask([mask] * hkv), block_sizes=sizes,
            residual_checkpoint_name=_SAVED)
        out = jax.vmap(call)(
            jnp.transpose(q[:, :, :, 0], (0, 2, 1, 3)),
            jnp.transpose(k, (0, 2, 1, 3)),
            jnp.transpose(v, (0, 2, 1, 3)))             # [B, H, S, Dv]
        return jnp.transpose(out, (0, 2, 1, 3))[:, :, :, None]
    call = kernel.make_splash_mqa_single_device(
        masks.MultiHeadMask([mask] * g), block_sizes=sizes,
        residual_checkpoint_name=_SAVED)
    out = jax.vmap(jax.vmap(call))(
        jnp.transpose(q, (0, 2, 3, 1, 4)), jnp.transpose(k, (0, 2, 1, 3)),
        jnp.transpose(v, (0, 2, 1, 3)))                 # [B, Hkv, G, S, D]
    return jnp.transpose(out, (0, 3, 1, 2, 4))


def use_kernels(kernels: Optional[bool]) -> bool:
    """`kernels` where it is given; else whether the process's default
    backend is a TPU."""
    return jax.default_backend() == "tpu" if kernels is None else kernels


def rms_normed(x, scale, eps: float, dtype):
    """`x / rms(x) * scale` over the last axis in float32, rounded once."""
    x = x.astype(jnp.float32)
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * scale).astype(dtype)


class RMSNorm(nn.Module):
    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        with jax.named_scope("rms_norm"):
            return rms_normed(x, scale, self.eps, self.dtype)


@jax.custom_vjp
def gated_output(out, gate):
    """`out * sigmoid(gate)` entry by entry in float32, rounded once to
    `out.dtype`: the gate on the attention's output. ONE pass of its own
    forward (reads both, writes the product) and one backward (reads both
    and the cotangent, writes two cotangents), held apart from the kernel
    before it and the products around it by `optimization_barrier`s. Left to
    itself XLA writes the kernel's output out again in float32, runs the
    forward multiply inside `gate_proj`'s product and the backward pass
    inside `o_proj`'s, under their names. The backward pass is written out
    so that both cotangents leave one fusion; it keeps `out` and `gate` and
    computes the sigmoid again."""
    out, gate = lax.optimization_barrier((out, gate))
    share = jax.nn.sigmoid(gate.astype(jnp.float32))
    return lax.optimization_barrier(
        (out.astype(jnp.float32) * share).astype(out.dtype))


def _gated_output_fwd(out, gate):
    return gated_output(out, gate), (out, gate)


def _gated_output_bwd(res, g):
    out, gate, g = lax.optimization_barrier((*res, g))
    g = g.astype(jnp.float32)
    share = jax.nn.sigmoid(gate.astype(jnp.float32))
    d_gate = g * out.astype(jnp.float32) * (share * (1.0 - share))
    return lax.optimization_barrier(
        ((g * share).astype(out.dtype), d_gate.astype(gate.dtype)))


gated_output.defvjp(_gated_output_fwd, _gated_output_bwd)


class Attention(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: Optional[int]           # None: a full layer
    inv_freq: Tuple[float, ...]
    rope_scale: float
    kernels: Optional[bool]
    dtype: Any
    qk_norm: bool = False           # RMSNorm over each q and k head, one
    qk_norm_eps: float = 1e-6       # learned scale each, BEFORE the turn
    positions: bool = True          # False: q and k are NOT turned
    gate: bool = False              # `gate_proj`, hidden -> heads x head_dim:
    # its sigmoid times the attention's output, entry by entry, before
    # `o_proj`; the module then answers (output, the sigmoid's mean)

    @nn.compact
    def __call__(self, x):
        b, s, hidden = x.shape
        hq, hkv, d = self.num_heads, self.num_kv_heads, self.head_dim

        def proj(name, heads):
            return nn.DenseGeneral((heads, d), use_bias=False,
                                   dtype=self.dtype, kernel_init=_INIT,
                                   name=name)(x)

        def placed(name, heads, out_scale=1.0):
            """The projection, normed where heads are, turned by its
            position where positions are, times `out_scale`: float32 from
            the last of them, rounded once."""
            y = proj(name + "_proj", heads)
            last = self.dtype if self.positions else jnp.float32
            if self.qk_norm:
                scale = self.param(name + "_layernorm", nn.initializers.ones,
                                   (d,), jnp.float32)
                with jax.named_scope("qk_norm"):
                    y = rms_normed(y, scale, self.qk_norm_eps, last)
            if self.positions:
                return apply_rope(y, self.inv_freq, self.rope_scale,
                                  out_scale=out_scale, dtype=self.dtype)
            return (y.astype(jnp.float32) * out_scale).astype(self.dtype)

        with jax.named_scope("attn_proj"):
            q = placed("q", hq, d ** -0.5).reshape(b, s, hkv, hq // hkv, d)
            k = placed("k", hkv)
            v = proj("v_proj", hkv)
            gate = proj("gate_proj", hq) if self.gate else None
        with jax.named_scope("attn_window" if self.window else "attn_full"):
            if use_kernels(self.kernels):
                out = splash_attention(q, k, v, self.window)
            else:
                out = plain_attention(q, k, v, self.window)
        with jax.named_scope("attn_proj"):
            out, share = out.reshape(b, s, hq, d), None
            if self.gate:
                with jax.named_scope("attn_gate"):
                    out = gated_output(out, gate)
                    share = jnp.mean(jax.nn.sigmoid(
                        lax.stop_gradient(gate).astype(jnp.float32)))
            y = nn.DenseGeneral(hidden, axis=(-2, -1), use_bias=False,
                                dtype=self.dtype, kernel_init=_INIT,
                                name="o_proj")(out)
        return (y, share) if self.gate else y


# ----------------------------------------------------------------- experts

@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def to_rows(x, first, inverse, live, top: int):
    """The token's row for each of the sorted assignments `first` [rows]
    (assignment a is token `a // top`): `x[first // top]`. Its cotangent
    comes back by `_summed`, `to_tokens`' sum with every weight 1 (under
    this function's scope, `moe_to_rows`)."""
    with jax.named_scope("moe_to_rows"):
        return x[first // top]


def _to_rows_fwd(x, first, inverse, live, top):
    return to_rows(x, first, inverse, live, top), (first, inverse, live)


def _to_rows_bwd(top, res, g):
    first, inverse, live = res
    with jax.named_scope("moe_to_rows"):
        return (_summed(g, None, inverse, live, top).astype(g.dtype),
                None, None, None)


to_rows.defvjp(_to_rows_fwd, _to_rows_bwd)


def _blocks(tokens: int, cap: int) -> Tuple[int, int, int]:
    """How `_summed` cuts `tokens` tokens over `cap` sorted rows: (blocks,
    tokens a block, the slots a block has for its live rows). `_ROOM` slots
    a block, and as many blocks as make `cap` slots in all: a block has
    twice an even load's rows, as `cap` has."""
    room = min(_ROOM, cap)
    blocks = max(1, cap // room)
    return blocks, -(-tokens // blocks), room


def _by_block(a, top: int, cap: int):
    """`a` [tokens * top] by `_blocks`' block of tokens: [blocks, tokens a
    block * top], zeros after the last token."""
    blocks, per, _ = _blocks(a.shape[0] // top, cap)
    return jnp.pad(a, (0, blocks * per * top - a.shape[0])).reshape(
        blocks, per * top)


def _fullest(live, top: int, cap: int):
    """The live assignments of the block of tokens that has most."""
    return jnp.max(jnp.sum(_by_block(live, top, cap), axis=1,
                           dtype=jnp.int32))


def room_used(cap: int, top: int, inverse, sizes):
    """The share of its room that the layer's load takes: the live rows
    over `cap` or, where `cap` is not room for all, the fullest block of
    tokens' live rows over its slots (`_blocks`) if that is more. Up to 1.0
    the `cap` rows hold every live one and `_summed` reads `cap` rows; over
    it the sum, or with more live rows than `cap` the whole layer
    (`_by_rows`), goes by a row for every assignment."""
    held = jnp.sum(sizes)
    used = held / cap
    if cap < inverse.shape[0]:
        room = _blocks(inverse.shape[0] // top, cap)[2]
        used = jnp.maximum(used, _fullest(inverse < held, top, cap) / room)
    return used.astype(jnp.float32)


def _summed(r, scale, inverse, live, top: int):
    """`to_tokens`' sum, under the scope of whoever calls it; `scale` None:
    every weight 1. float32 [T, h].

    Where `r` has a row for every assignment (`cap == T * top`: all experts
    held, or the `large` side of `_by_rows`) every row may be live, and
    each assignment's row is gathered (`_gathered`). Where it has fewer,
    that gather reads `T * top` rows to zero most (the assignments of
    absent experts: 3 in 4 at `T * top / cap` 4, 15 in 16 at 16), and the
    sum reads `cap` rows, the number that is there (`_banded`), wherever
    every block of tokens' live rows fit the block's slots: which the step
    can see, and the gather is there for the step where they do not (a run
    of tokens that choose held experts: 2 of 102 logged steps of
    `mellum2_moe_dp1` read `room_used` 1.06 and 1.09). One path for every ratio
    `T * top / cap`; on the chip, ms a call with weights / with none, T
    16 384 (`PERF.md` section 6, PR 39, has every form tried): 1.9-2.1 /
    1.75-1.85 at top 8, cap 32 768, h 2304 (ratio 4) where the gather
    alone takes 7.5 and 4.75 fused into the step; 0.5-0.8 / 0.35-0.45 for
    1.8 at cap 8 192, h 2048 (ratio 16); 1.7-1.9 / 1.6 for 4.1 at top 4,
    cap 32 768, h 2048 (ratio 2)."""
    cap, full = r.shape[0], inverse.shape[0]
    if cap == full:
        return _gathered(scale is None, top, r, scale, inverse, live)
    return lax.cond(_fullest(live, top, cap) <= _blocks(full // top, cap)[2],
                    functools.partial(_banded, scale is None, top),
                    functools.partial(_gathered, scale is None, top),
                    r, scale, inverse, live)


def _gathered(ones: bool, top: int, r, scale, inverse, live):
    """`_summed` by a gathered row for every assignment."""
    picked = jnp.where(live[:, None],
                       r[jnp.minimum(inverse, r.shape[0] - 1)],
                       jnp.zeros((), r.dtype))
    scale = jnp.ones(inverse.shape, r.dtype) if ones else scale
    return jnp.einsum("tkh,tk->th", picked.reshape(-1, top, r.shape[-1]),
                      scale.reshape(-1, top),
                      preferred_element_type=jnp.float32)


def _banded(ones: bool, top: int, r, scale, inverse, live):
    """`_summed` over `r`'s `cap` rows, where every block's live rows fit
    its slots. The live assignments are in token order along `a = t * top +
    j` already, so a live assignment's place among its block of tokens'
    live ones is a prefix count; the block's rows are gathered into its
    slots by those places, and the block's sum is its [tokens, slots]
    matrix of weights (0 where a slot is not that token's) times its rows:
    float32 weights times the rows widened to float32 at `highest`, which
    the matrix unit computes exactly (a weight split into bfloat16 parts by
    casts is NOT kept apart on the TPU, whose compiler drops a rounding to
    bfloat16 and back, and the product of such parts was the slower one)."""
    (cap, h), tokens = r.shape, inverse.shape[0] // top
    blocks, per, room = _blocks(tokens, cap)
    live = _by_block(live, top, cap)
    lives = live.astype(jnp.int32)
    counts = jnp.sum(lives, axis=1)
    # a live assignment's slot: its place among its block's live ones
    slot = jnp.where(live, jnp.cumsum(lives, axis=1) - lives, room)
    hit = slot.reshape(blocks, per, top, 1) == jnp.arange(room)
    # the sorted row in each slot (one assignment hits a slot, or none)
    window = jnp.sum(jnp.where(hit, _by_block(inverse, top, cap).reshape(
        blocks, per, top, 1), 0), axis=(1, 2))          # [blocks, room]
    mine = jnp.arange(room)[None, :] < counts[:, None]
    rows = jnp.where(mine[:, :, None], r[window], jnp.zeros((), r.dtype))
    if ones:
        weights = jnp.any(hit, axis=2).astype(r.dtype)
    else:
        scale = _by_block(scale.astype(jnp.float32), top, cap).reshape(
            blocks, per, top)
        weights = jnp.sum(jnp.where(hit, scale[..., None], 0.0), axis=2)
    y = jnp.einsum("btc,bch->bth", weights, rows.astype(weights.dtype),
                   precision=lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)
    return y.reshape(blocks * per, h)[:tokens]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def to_tokens(r, scale, first, inverse, live, top: int):
    """For every token the sum over its `top` assignments a of `scale[a]`
    times the row `r[inverse[a]]` that assignment a was sorted to, over the
    `live` assignments (those of held experts, sorted before `r`'s end),
    in float32."""
    with jax.named_scope("moe_to_tokens"):
        return _summed(r, scale, inverse, live, top)


def _to_tokens_fwd(r, scale, first, inverse, live, top):
    return (to_tokens(r, scale, first, inverse, live, top),
            (r, scale, first, inverse, live))


def _to_tokens_bwd(top, res, g):
    r, scale, first, inverse, live = res
    with jax.named_scope("moe_to_tokens"):
        # in the sorted rows' order: every live row has one assignment
        sorted_live = (jnp.arange(first.shape[0]) < jnp.sum(live))[:, None]
        g_rows = jnp.where(sorted_live, g[first // top], 0.0)
        d_r = (g_rows * scale[first][:, None].astype(g.dtype)
               ).astype(r.dtype)
        d_sorted = jnp.sum(g_rows * r.astype(g.dtype), axis=-1)
        d_scale = jnp.where(
            live, d_sorted[jnp.minimum(inverse, first.shape[0] - 1)], 0.0)
        return d_r, d_scale.astype(scale.dtype), None, None, None


to_tokens.defvjp(_to_tokens_fwd, _to_tokens_bwd)


def _live_rows(x, sizes):
    """`x` with the rows past the last group's end zeroed: they belong to
    absent experts, and a grouped product leaves them as it finds them
    (on the TPU: uninitialised)."""
    live = jnp.arange(x.shape[0]) < jnp.sum(sizes)
    return jnp.where(live[:, None], x, jnp.zeros((), x.dtype))


# the weights' cotangent: rows of one group contracted, a group at a time
_BY_GROUP = lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(([0], [0]), ([], [])),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _grouped(kernels: bool, name: str, a, b, sizes, transposed=False):
    """One grouped product over the rows `a`, float32 accumulated. `b` [E,
    k, n]: `a[rows of e] @ b[e]`, or `@ b[e].T` if `transposed`, as
    `a.dtype`; `b` rows [m, n]: `a[rows of e].T @ b[rows of e]` for every
    group, float32. With `kernels`, `ops/grouped_matmul.py`'s kernel `name`
    on the tiles it computes from the shape, under the scope `moe_product`;
    without, or for a shape it has no tiles for, `lax.ragged_dot` under
    `moe_product_glue` (on a TPU that is the compiler's own kernel, which
    carries no name at all)."""
    by_group = b.ndim == 2
    tiles = (grouped_matmul.tiles_by_group if by_group
             else grouped_matmul.tiles)
    if kernels and tiles(*a.shape, b.shape[1 if by_group or transposed
                                           else 2]):
        with jax.named_scope("moe_product"):
            if by_group:
                return grouped_matmul.grouped_by_group(a, b, sizes, name=name)
            return grouped_matmul.grouped(a, b, sizes, transposed=transposed,
                                          name=name)
    with jax.named_scope("moe_product_glue"):
        if by_group:
            return lax.ragged_dot_general(
                a, b, sizes, _BY_GROUP, preferred_element_type=jnp.float32)
        return lax.ragged_dot(a, jnp.swapaxes(b, 1, 2) if transposed else b,
                              sizes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_product(x, w, sizes, kernels: bool = False):
    """`x[rows of group e] @ w[e]` for every group, `w` the float32
    parameter, multiplied as `x.dtype` and accumulated in float32. Zero in
    the rows past the last group's, forward and backward: neither kernel
    writes them, what is there must not reach a sum, and 0 times it is no
    0. The weights' cotangent leaves the product in float32 (a bfloat16 one
    would round every gradient of an expert to 8 bits before it is
    accumulated). `kernels`: the three products (forward `grouped_fwd`, the
    rows' cotangent `grouped_dx` against the matrices as they lie, the
    weights' `grouped_dw`) run through `ops/grouped_matmul.py` (`_grouped`).
    Under the scope `moe_product_glue` is what is NOT a product's kernel:
    the casts, the zeroing and, without `kernels`, the transposed copy."""
    with jax.named_scope("moe_product_glue"):
        w = w.astype(x.dtype)
    y = _grouped(kernels, "grouped_fwd", x, w, sizes)
    with jax.named_scope("moe_product_glue"):
        return _live_rows(y, sizes)


def _grouped_fwd(x, w, sizes, kernels):
    return grouped_product(x, w, sizes, kernels), (x, w, sizes)


def _grouped_bwd(kernels, res, g):
    x, w, sizes = res
    with jax.named_scope("moe_product_glue"):
        g = _live_rows(g, sizes)
        wx = w.astype(x.dtype)
    dx = _grouped(kernels, "grouped_dx", g, wx, sizes, transposed=True)
    dw = _grouped(kernels, "grouped_dw", x, g, sizes)
    with jax.named_scope("moe_product_glue"):
        return _live_rows(dx, sizes), dw.astype(w.dtype), None


grouped_product.defvjp(_grouped_fwd, _grouped_bwd)


def _terms(cap: int, top: int, kernels: bool, x, weights, order, inverse,
           sizes, w1, w3, w2):
    """The held experts' part of the layer's output, float32 [T, h], over
    the first `cap` sorted assignments, which hold every live one."""
    first = order[:cap]
    live = inverse < jnp.sum(sizes)
    rows = to_rows(x, first, inverse, live, top)
    gate = grouped_product(rows, w1, sizes, kernels)
    up = grouped_product(rows, w3, sizes, kernels)
    with jax.named_scope("moe_gate"):
        gated = jax.nn.silu(gate) * up
    out = grouped_product(gated, w2, sizes, kernels)
    return to_tokens(out, weights.reshape(-1), first, inverse, live, top)


def _by_rows(enough: int, sizes, small, large, *args):
    """`small(*args)` where the live rows fit `enough`, else `large`."""
    return lax.cond(jnp.sum(sizes) <= enough, small, large, *args)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def expert_terms(enough: int, top: int, kernels: bool, x, weights, order,
                 inverse, sizes, w1, w3, w2):
    """`_terms` with room for `enough` rows where the live ones fit and for
    all `T * top` where not. A `lax.cond` that is differentiated through
    keeps BOTH sides' residuals (7 GB more at the benchmark's size), so the
    choice is made again in the backward pass: the small side keeps what
    its backward pass needs, the large side (an uneven load, seldom taken)
    keeps nothing and is recomputed from the arguments."""
    return _expert_terms_fwd(enough, top, kernels, x, weights, order,
                             inverse, sizes, w1, w3, w2)[0]


def _floats_of(cap, top, kernels, order, inverse, sizes):
    """`_terms` as a function of what it is differentiated by."""
    return lambda x, weights, w1, w3, w2: _terms(
        cap, top, kernels, x, weights, order, inverse, sizes, w1, w3, w2)


def _expert_terms_fwd(enough, top, kernels, *args):
    x, weights, order, inverse, sizes, w1, w3, w2 = args
    floats, full = (x, weights, w1, w3, w2), order.shape[0]
    if enough >= full:
        y, back = jax.vjp(
            _floats_of(full, top, kernels, order, inverse, sizes), *floats)
        return y, (back, args)

    def small(*args):
        x, weights, order, inverse, sizes, w1, w3, w2 = args
        return jax.vjp(
            _floats_of(enough, top, kernels, order, inverse, sizes),
            x, weights, w1, w3, w2)

    # the backward function is a pytree: its leaves are what it keeps, and
    # the two sides of a `cond` have to hand out the same leaves
    kept, function = jax.tree.flatten(jax.eval_shape(small, *args)[1])

    def small_kept(*args):
        y, back = small(*args)
        return y, jax.tree.leaves(back)

    def large_kept(*args):
        return (_terms(full, top, kernels, *args),
                [jnp.zeros(r.shape, r.dtype) for r in kept])

    y, leaves = _by_rows(enough, sizes, small_kept, large_kept, *args)
    return y, (jax.tree.unflatten(function, leaves), args)


def _expert_terms_bwd(enough, top, kernels, res, g):
    back, args = res
    full, sizes = args[2].shape[0], args[4]

    def recomputed(back, g, *args):
        x, weights, order, inverse, sizes, w1, w3, w2 = args
        return jax.vjp(
            _floats_of(full, top, kernels, order, inverse, sizes),
            x, weights, w1, w3, w2)[1](g)

    if enough >= full:
        dx, dweights, dw1, dw3, dw2 = back(g)
    else:
        dx, dweights, dw1, dw3, dw2 = _by_rows(
            enough, sizes, lambda back, g, *args: back(g), recomputed,
            back, g, *args)
    return dx, dweights, None, None, None, dw1, dw3, dw2


expert_terms.defvjp(_expert_terms_fwd, _expert_terms_bwd)


def route(probs, top: int, first: int, held: int, choose_by=None,
          scale: float = 1.0, sum_eps: float = 0.0):
    """From the router's scores [T, E] (softmax probabilities, or a sigmoid
    of each logit): each token's `top` largest by `choose_by` [T, E] where
    that is given (the scores plus a bias that only selects) and by the
    scores themselves where not; their scores renormalised to sum 1 (divided
    by their sum plus `sum_eps`), times `scale`; which rows of the `T * top`
    assignments go to which of the `held` experts from `first` on.

    Returns (weights [T, top]; `order` [T * top], the assignments sorted by
    held expert, those of absent experts last; its inverse; `sizes`
    [held], the rows each held expert got; `served` [T], whether any of a
    token's experts is held)."""
    if choose_by is None:
        weights, experts = lax.top_k(probs, top)
    else:
        _, experts = lax.top_k(choose_by, top)
        weights = jnp.take_along_axis(probs, experts, axis=-1)
    total = jnp.sum(weights, axis=-1, keepdims=True)
    weights = weights / (total + sum_eps if sum_eps else total)
    if scale != 1.0:
        weights = weights * scale
    local = experts - first
    mine = (local >= 0) & (local < held)
    group = jnp.where(mine, local, held).reshape(-1)
    with jax.named_scope("moe_route_sort"):
        order = jnp.argsort(group, stable=True)
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        sizes = jnp.sum(group[:, None] == jnp.arange(held)[None, :], axis=0,
                        dtype=jnp.int32)
    return weights, order, inverse, sizes, jnp.any(mine, axis=-1)


class GatedMLP(nn.Module):
    """`W2 (silu(W1 x) * W3 x)` on the last axis, under the device scope
    `device_scope`: float32 parameters multiplied as `x.dtype`."""
    width: int
    device_scope: str

    @nn.compact
    def __call__(self, x):
        wide = (x.shape[-1], self.width)
        w1 = self.param("w1", _INIT, wide, jnp.float32).astype(x.dtype)
        w3 = self.param("w3", _INIT, wide, jnp.float32).astype(x.dtype)
        w2 = self.param("w2", _INIT, wide[::-1], jnp.float32).astype(x.dtype)
        with jax.named_scope(self.device_scope):
            return jnp.dot(jax.nn.silu(jnp.dot(x, w1)) * jnp.dot(x, w3), w2)


class Experts(nn.Module):
    """The router over all `num_experts` and the gated experts held here.
    `scoring` `softmax`: probabilities over all experts; `sigmoid`: each
    logit's own. With `select_bias` a leaf `router_bias` is added to the
    scores where the `experts_per_token` are CHOSEN and nowhere else (its
    gradient is exactly zero: a selection has none). The chosen scores are
    renormalised (their sum plus `sum_eps` the divisor) and multiplied by
    `scale`. `shared_width` > 0: a gated expert of that width that every
    token passes, added by every share."""
    num_experts: int
    experts_per_token: int
    width: int
    share: int
    shares: int
    dtype: Any
    scoring: str = "softmax"
    select_bias: bool = False
    scale: float = 1.0
    shared_width: int = 0
    sum_eps: float = 0.0
    kernels: Optional[bool] = None  # None: where the backend is a TPU

    @nn.compact
    def __call__(self, x):
        b, s, hidden = x.shape
        tokens, top = b * s, self.experts_per_token
        if self.num_experts % self.shares or not (
                0 <= self.share < self.shares):
            raise ValueError(
                f"share {self.share} of {self.shares} does not divide "
                f"{self.num_experts} experts")
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring {self.scoring!r}")
        held = self.num_experts // self.shares
        x = x.reshape(tokens, hidden)
        with jax.named_scope("moe_router"):
            # float32 throughout: a near tie decides which expert is paid
            router = self.param("router", _INIT, (hidden, self.num_experts),
                                jnp.float32)
            logits = jnp.dot(x.astype(jnp.float32), router,
                             precision=lax.Precision.HIGHEST)
            scores = (jax.nn.softmax(logits, axis=-1)
                      if self.scoring == "softmax"
                      else jax.nn.sigmoid(logits))
            choose_by = None
            if self.select_bias:
                choose_by = scores + self.param(
                    "router_bias", nn.initializers.zeros,
                    (self.num_experts,), jnp.float32)
            weights, order, inverse, sizes, served = route(
                scores, top, self.share * held, held, choose_by, self.scale,
                self.sum_eps)
        shape = (held, hidden, self.width)
        w1 = self.param("w1", _INIT, shape, jnp.float32)
        w3 = self.param("w3", _INIT, shape, jnp.float32)
        w2 = self.param("w2", _INIT, (held, self.width, hidden), jnp.float32)
        with jax.named_scope("moe_experts"):
            # Room for every assignment (all of a token's experts held)
            # costs gathers of `tokens * top` rows; an even load fills a
            # `shares`-th of them. So: twice the even load's rows where
            # they suffice, which the step can see, else all of them. No
            # token is dropped on either side.
            full = tokens * top
            enough = min(full, -(-2 * full // self.shares // 8) * 8)
            y = expert_terms(enough, top, use_kernels(self.kernels), x,
                             weights, order, inverse, sizes, w1, w3, w2)
        if self.shared_width:
            y = y + GatedMLP(self.shared_width, "moe_shared",
                             name="shared")(x).astype(jnp.float32)
        load = sizes.astype(jnp.float32)
        counters = {
            "moe_held_assignments": jnp.sum(load),
            "moe_room_used": room_used(enough, top, inverse, sizes),
            "moe_load_max_over_mean": jnp.max(load) / jnp.maximum(
                jnp.mean(load), 1.0),
            "moe_tokens_unserved": 1.0 - jnp.mean(served.astype(jnp.float32))}
        return y.astype(self.dtype).reshape(b, s, hidden), counters


def own_fields(module: nn.Module) -> types.SimpleNamespace:
    """A model's own fields as a namespace for its layers: a module may not
    be another's field, so the layers get the numbers."""
    return types.SimpleNamespace(**{
        f.name: getattr(module, f.name) for f in dataclasses.fields(module)
        if f.name not in ("parent", "name")})


def model_counters(per_layer):
    """The model's counters from its expert layers': the assignments held
    summed, the room used and the load of the worst layer, the unserved
    share's mean."""
    stacked = jax.tree.map(lambda *v: jnp.stack(v), *per_layer)
    return {
        "moe_held_assignments": jnp.sum(stacked["moe_held_assignments"]),
        "moe_room_used": jnp.max(stacked["moe_room_used"]),
        "moe_load_max_over_mean": jnp.max(
            stacked["moe_load_max_over_mean"]),
        "moe_tokens_unserved": jnp.mean(stacked["moe_tokens_unserved"])}


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
        kinds = tuple(self.layer_types or _PERIOD * (self.num_layers // 4 + 1)
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
        # a layer is recomputed in its backward pass, but for `_SAVED`
        layer = nn.remat(Layer, policy=jax.checkpoint_policies
                         .save_only_these_names(_SAVED))
        widths = own_fields(self)
        per_layer = []
        for i, kind in enumerate(kinds):
            x, counters = layer(
                widths, self.sliding_window if kind == SLIDING else None,
                name=f"layers_{i}")(x)
            per_layer.append(counters)
        x = RMSNorm(self.rms_norm_eps, self.dtype, name="norm")(x)
        with jax.named_scope("lm_head"):
            head = self.param("lm_head", _INIT,
                              (self.hidden_size, self.vocab_size),
                              jnp.float32)
            logits = jnp.dot(x, head.astype(self.dtype),
                             preferred_element_type=jnp.float32)
        if not return_counters:
            return logits
        return logits, model_counters(per_layer)
