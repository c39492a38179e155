"""How attention is tiled and masked, and what a recomputed layer keeps of
it. Causal, each key/value head serving a group of query heads; a window
layer sees keys `0 <= i - j < window`, a full layer all earlier keys.

Attention never builds `[S, S]`. With `kernels` (the default on a TPU) it is
the Pallas splash-attention kernel of `jax.experimental`, which skips the
blocks a mask leaves empty, so a window layer's work goes with S x window,
on tiles and in the form that `splash_sizes` computes from the call's shape
(a full layer's backward pass is ONE kernel call, a window layer's two);
elsewhere (the CPU tests) blocks of queries against the keys their mask can
reach."""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from .common import (INIT, norm_scale_init, rms_normed, scaled_init,
                     use_kernels)
from .rope import apply_rope

# the two kinds of attention layer, as a config's `layer_types` names them
SLIDING, FULL = "sliding_attention", "full_attention"
PERIOD = (SLIDING, SLIDING, SLIDING, FULL)  # where no `layer_types` is given

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


def recomputed(layer):
    """`layer` (a module class) recomputed in its backward pass, but for
    `_SAVED`."""
    return nn.remat(layer, policy=jax.checkpoint_policies
                    .save_only_these_names(_SAVED))


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
    keys, which costs 1-2 %. A head over 192 lanes (256) takes the fused
    kernel's queries in blocks of half as many rows too: at 512 the kernel
    alone compiles, but in a step whose neighbours leave the compiler
    operands to place in fast memory it is refused its stack (PERF.md
    section 7, PR 44 open (3); met by PR 45's step)."""
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
        block_q_dkv=blk // 2 if d > 192 and blk == _SPLASH_BLOCK else blk,
        block_kv_dkv=fused[0],
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
    qk_norm_zero_centred: bool = False  # that norm times `1 + scale`
    positions: bool = True          # False: q and k are NOT turned
    rope_lead: bool = False         # the turn takes a head's FIRST entries
    gate: bool = False              # `gate_proj`, hidden -> heads x head_dim:
    # its sigmoid times the attention's output, entry by entry, before
    # `o_proj`; the module then answers (output, the sigmoid's mean)
    out_init_scale: float = 1.0     # `o_proj`'s draw at init times this

    @nn.compact
    def __call__(self, x):
        b, s, hidden = x.shape
        hq, hkv, d = self.num_heads, self.num_kv_heads, self.head_dim

        def proj(name, heads):
            return nn.DenseGeneral((heads, d), use_bias=False,
                                   dtype=self.dtype, kernel_init=INIT,
                                   name=name)(x)

        def placed(name, heads, out_scale=1.0):
            """The projection, normed where heads are, turned by its
            position where positions are, times `out_scale`: float32 from
            the last of them, rounded once."""
            y = proj(name + "_proj", heads)
            last = self.dtype if self.positions else jnp.float32
            if self.qk_norm:
                scale = self.param(
                    name + "_layernorm",
                    norm_scale_init(self.qk_norm_zero_centred), (d,),
                    jnp.float32)
                with jax.named_scope("qk_norm"):
                    y = rms_normed(y, scale, self.qk_norm_eps, last,
                                   self.qk_norm_zero_centred)
            if self.positions:
                return apply_rope(y, self.inv_freq, self.rope_scale,
                                  out_scale=out_scale, dtype=self.dtype,
                                  lead=self.rope_lead)
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
                                dtype=self.dtype,
                                kernel_init=scaled_init(self.out_init_scale),
                                name="o_proj")(out)
        return (y, share) if self.gate else y
