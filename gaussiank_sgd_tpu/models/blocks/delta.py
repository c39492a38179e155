"""A mixer that carries a state along the sequence: the gated delta rule
(Yang, Kautz and Hatamizadeh, "Gated Delta Networks", arXiv:2412.06464), as
transformers' `Qwen3NextGatedDeltaNet` lays it out.

A value head keeps a matrix `S` [key size, value size], zero before the
sequence, float32. Token t decays it by `exp(g_t)` (`g_t <= 0`), reads what
it holds under the key `k_t`, writes the difference to the value `v_t` back
with strength `beta_t`, and answers the query `q_t`:

    S_t = exp(g_t) S_{t-1} + k_t (beta_t (v_t - exp(g_t) S_{t-1}^T k_t))^T
    o_t = S_t^T q_t

(the same as `S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t
v_t^T`). `recurrent_rule` is that sentence, token by token: the rule's own
statement, what the CPU tests hold everything else to, and not what a chip
should run (8192 dependent steps of a few vector operations each).

**What runs where.** On a TPU (`GatedDeltaNet.kernels`: None means "where
the backend is a TPU", as `Attention` and `Experts` take it), at key and
value heads of whole 128-lane blocks and a sequence of whole chunks, the
mixer between its two products is five Pallas calls a layer and step:

  - what lies between `in_proj_qkvz` and the rule,
    `ops/delta_prologue.conv_norm` (PR 47): `gdn_conv_fwd` reads the columns
    of `[q | k | v]` where they lie in `qkvz`, computes the taps' sum once,
    `silu`, and the L2 norms of q's and k's heads on what it holds, and
    writes q, k and v as the rule's kernels read them (`[B, S, heads x
    128]`); `gdn_conv_bwd` computes the same again from `qkvz` and the taps,
    ALL it keeps (no float32 copy of q or k), and writes `qkvz`'s cotangent
    and the taps';
  - the rule, `ops/delta_rule.gated_delta_rule` (PR 45): the chunk algebra
    below in three kernels, `gdn_fwd`, `gdn_fwd_kept` and `gdn_bwd`, with
    the state in VMEM from a sequence's first chunk to its last, q, k, v, g
    and beta read once a pass, and the inverse computed in the kernel.

A differentiated layer under `jax.checkpoint` runs `gdn_conv_fwd` and
`gdn_fwd_kept` in its forward pass and again in the recomputed one, then
`gdn_bwd` and `gdn_conv_bwd`. Any other shape, and every CPU run, takes
`conv_silu`, `l2_normed` and `chunked_rule`: the same arithmetic with the
same roundings in XLA's own operations, the statements the kernels are tested
against (`tests/test_delta_prologue.py`, `tests/test_delta_kernels.py`,
beside `recurrent_rule`). One choice by shape for both families: a shape
that either refuses runs no kernel.

`chunked_rule` computes the same in chunks of `CHUNK` tokens, with the state
carried between chunks and never a `[S, S]` matrix. Within a chunk, with `G`
the running sum of `g` and `decay[i, j] = exp(G_i - G_j)` for `j <= i` (only
differences that are <= 0 are ever exponentiated: no overflow at any `g`):

    A      = strictly_lower(diag(beta) (K K^T * decay))
    [W | U] = (I + A)^-1 diag(beta) [K exp(G) | V]            (WY / UT form)
    V'     = U - W S                          what the chunk really writes
    O      = (Q exp(G)) S + lower(Q K^T * decay) V'
    S     <- exp(G_C) S + (K exp(G_C - G))^T V'

Everything but the last three lines is the same work for every chunk at
once (batched products); the last three are a `lax.scan` over the chunks
with `S` in float32. The products take their operands in `dtype` (the
model's compute dtype) and accumulate in float32; the inverse of the unit
lower-triangular `I + A` is float32 at `highest` throughout
(`unit_lower_inverse`: forward substitution on 16 x 16 diagonal blocks, the
blocks joined by products, its cotangent two products with itself).

The backward pass is the scan's own transpose with the chunk's body
recomputed: it keeps every chunk's incoming state (`[chunks, B, H, key,
value]` float32: 2 x 32 x 128 states of 64 kB are 537 MB a layer at 2 x 8192
tokens) and what the scan reads (five arrays of `[tokens, H, 128]` or less
as `dtype`: 0.74 GB), and computes the chunks' batched part again from q, k,
v, g and beta. Differentiated as written it kept every float32 factor of
every elementwise product (3.6 GB of temporaries for the rule alone, device-
less for a v5e; PERF.md section 6, PR 44). All of it lives only while the
one layer that is being differentiated is recomputed
(`attention.recomputed`).

`GatedDeltaNet` is the mixer whole: one product to `[q | k | v | z]` and a
small one to `[b | a]`, a depthwise causal convolution of a few taps with
SiLU over `[q | k | v]` (`conv_silu`, shifted multiply-adds as
`lfm2_moe.gated_taps`), L2-normed q and k, `beta = sigmoid(b)`, `g =
-exp(A_log) softplus(a + dt_bias)`, the rule, an RMSNorm over each head's
output gated by `silu(z)` (`normed_gate`), the output product.

Device scopes: `linear_attn` around the mixer; inside it `gdn_in_proj`,
`gdn_conv` (the convolution with SiLU; with the kernels also the L2 norms
and the scale of q and k: `gdn_conv_fwd` and `gdn_conv_bwd`, whose `op_name`
ends in `gdn_conv/<kernel>/pallas_call`), `gdn_rule` (the gates and the rule:
the three kernels, whose `op_name` ends in `gdn_rule/<kernel>/pallas_call`;
without the kernels the L2 norms, the chunks' solve and the scan),
`gdn_norm_gate`, `gdn_out_proj`. Without the kernels `gdn_conv`, and either
way `gdn_norm_gate`, are passes of their own (`optimization_barrier`): left
alone XLA runs them inside the products beside them, under those products'
names. Counters (the module's
second output): `gdn_decay_mean` (mean of `exp(g)`), `gdn_beta_mean`,
`gdn_state_rms` (root mean square of the state after the last token).
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from ...ops import delta_prologue, delta_rule
from .common import INIT, shifted, use_kernels

CHUNK = delta_rule.CHUNK    # tokens to a chunk, of the kernels' and here (64)
_SUBSTITUTED = 16       # the diagonal blocks that are inverted row by row
_L2_EPS = delta_prologue.L2_EPS   # in the L2 norm of q and k (1e-6)
_HIGHEST = lax.Precision.HIGHEST


def recurrent_rule(q, k, v, g, beta):
    """The rule token by token. q, k [B, T, Hk, dk] (normed, q scaled;
    each key head serves `H / Hk` value heads, neighbours together), v [B,
    T, H, dv], g and beta [B, T, H]; everything float32. Returns (o [B, T,
    H, dv], the state after the last token [B, H, dk, dv])."""
    b, _, h, _ = v.shape
    dk = q.shape[-1]
    q, k = (jnp.repeat(a, h // a.shape[2], axis=2) for a in (q, k))

    def step(state, token):
        q_t, k_t, v_t, g_t, beta_t = token
        state = state * jnp.exp(g_t)[..., None, None]
        held = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=_HIGHEST)
        write = (v_t - held) * beta_t[..., None]
        state = state + k_t[..., :, None] * write[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t,
                                 precision=_HIGHEST)

    tokens = [jnp.moveaxis(a.astype(jnp.float32), 1, 0)
              for a in (q, k, v, g, beta)]
    state, o = lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
                        tokens)
    return jnp.moveaxis(o, 0, 1), state


def _substituted(a):
    """`(I + a)^-1` for a strictly lower `a` [..., c, c] by forward
    substitution, a row at a time: row i of the inverse (but for its 1) is
    `-a[i] - a[i] @ (the rows above, done)`. A loop of the program, not of
    its text: one body for the compiler."""
    c = a.shape[-1]

    def row(i, t):
        here = lax.dynamic_index_in_dim(t, i, axis=t.ndim - 2, keepdims=False)
        # `here` is zero from column i on, so only finished rows are read
        here = here + jnp.einsum("...j,...jk->...k", here, t,
                                 precision=_HIGHEST)
        return lax.dynamic_update_index_in_dim(t, here, i, axis=t.ndim - 2)

    return lax.fori_loop(1, c, row, -a) + jnp.eye(c, dtype=a.dtype)


def _inverse(a):
    c = a.shape[-1]
    if c <= _SUBSTITUTED:
        return _substituted(a)
    # [[m11, 0], [-m22 a21 m11, m22]] of the two halves' inverses; halves of
    # one size are inverted side by side, as one batch
    h = c // 2
    if c % 2:
        m11, m22 = _inverse(a[..., :h, :h]), _inverse(a[..., h:, h:])
    else:
        m11, m22 = _inverse(jnp.stack([a[..., :h, :h], a[..., h:, h:]]))
    m21 = -jnp.einsum("...ij,...jk,...kl->...il", m22, a[..., h:, :h], m11,
                      precision=_HIGHEST)
    top = jnp.concatenate([m11, jnp.zeros_like(a[..., :h, h:])], axis=-1)
    return jnp.concatenate(
        [top, jnp.concatenate([m21, m22], axis=-1)], axis=-2)


@jax.custom_vjp
def unit_lower_inverse(a):
    """`(I + a)^-1` for `a` [..., c, c] of which only the part strictly
    below the diagonal counts, float32. As stable as forward substitution
    (no power of `a` is formed). Its cotangent is `-M^T g M^T`, strictly
    lower: nothing of the substitution is kept but the inverse."""
    return _inverse(_strictly_lower(a))


def _strictly_lower(a):
    c = a.shape[-1]
    return jnp.where(jnp.tril(jnp.ones((c, c), bool), -1), a, 0.0)


def _unit_lower_inverse_fwd(a):
    m = unit_lower_inverse(a)
    return m, m


def _unit_lower_inverse_bwd(m, g):
    mt = jnp.swapaxes(m, -1, -2)
    return (_strictly_lower(-jnp.einsum(
        "...ij,...jk,...kl->...il", mt, g, mt, precision=_HIGHEST)),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _product(dtype, spec, x, y, out=jnp.float32):
    """`einsum` of operands held as `dtype`, summed in float32, handed out
    as `out`."""
    return jnp.einsum(
        spec, x.astype(dtype), y.astype(dtype),
        precision=_HIGHEST if dtype == jnp.float32 else None,
        preferred_element_type=jnp.float32).astype(out)


def _per_chunk(chunk: int, dtype, q, k, v, g, beta):
    """What the scan over chunks reads, each `[chunks, B, H, chunk, ...]`:
    `W`, `U`, `Q exp(G)`, `lower(Q K^T * decay)`, `K exp(G_C - G)` as
    `dtype` and `exp(G_C)` float32 (the module's head has the algebra):
    the work that is the same for every chunk, as batched products."""
    b, t, h, _ = v.shape
    fill = -t % chunk
    n = (t + fill) // chunk

    def chunks(a):
        """[B, T, H, ...] -> [chunks, B, H, chunk, ...], float32."""
        a = jnp.pad(a.astype(jnp.float32),
                    ((0, 0), (0, fill)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape(b, n, chunk, *a.shape[2:])
        return jnp.moveaxis(a, (1, 3), (0, 2))

    product = functools.partial(_product, dtype)
    q, k, v, g, beta = (chunks(a) for a in (q, k, v, g, beta))
    q, k = (jnp.repeat(a, h // a.shape[2], axis=2) for a in (q, k))
    run = jnp.cumsum(g, axis=-1)                            # G [n, B, H, C]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(
        lower, run[..., :, None] - run[..., None, :], -jnp.inf))
    k_beta = k * beta[..., None]
    inverse = unit_lower_inverse(
        product("...id,...jd->...ij", k_beta, k) * decay)
    w = product("...ij,...jd->...id", inverse,
                k_beta * jnp.exp(run)[..., None], out=dtype)
    u = product("...ij,...jd->...id", inverse, v * beta[..., None], out=dtype)
    within = product("...id,...jd->...ij", q, k) * decay
    last = run[..., -1:]
    rest = (q * jnp.exp(run)[..., None], within,
            k * jnp.exp(last - run)[..., None])
    return (w, u, *(a.astype(dtype) for a in rest), jnp.exp(last[..., 0]))


def chunked_rule(q, k, v, g, beta, chunk: int = CHUNK,
                 dtype: Any = jnp.float32):
    """`recurrent_rule` in chunks of `chunk` tokens (the module's head has
    the algebra). q, k [B, T, Hk, dk], v [B, T, H, dv], g and beta [B, T, H]
    float32. The products' operands are `dtype`, their sums and the state
    float32, the output `dtype`. A sequence that is no whole number of
    chunks is filled up with tokens that neither decay nor write. Both
    halves are recomputed in the backward pass (`jax.checkpoint`): of
    `_per_chunk` it keeps the five arguments, of the scan each chunk's
    incoming state. Returns (o [B, T, H, dv] as `dtype`, the state after
    the last token [B, H, dk, dv] float32)."""
    b, t, h, dv = v.shape
    dk = q.shape[-1]
    product = functools.partial(_product, dtype)

    @jax.checkpoint
    def step(state, chunk_of):
        w, u, q_decayed, within, k_left, kept = chunk_of
        written = u - product("bhck,bhkv->bhcv", w, state)
        o = (product("bhck,bhkv->bhcv", q_decayed, state)
             + product("bhcj,bhjv->bhcv", within, written))
        state = (kept[..., None, None] * state
                 + product("bhck,bhcv->bhkv", k_left, written))
        return state, o.astype(dtype)

    state, o = lax.scan(
        step, jnp.zeros((b, h, dk, dv), jnp.float32),
        jax.checkpoint(functools.partial(_per_chunk, chunk, dtype))(
            q, k, v, g, beta))
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, -1, h, dv)
    return o[:, :t], state


def _taps_sum(x, taps):
    """`sum_j taps[:, j] * x_{t - (L - 1 - j)}` in float32, zeros before the
    sequence starts: a causal depthwise convolution as shifted
    multiply-adds."""
    length = taps.shape[1]
    return sum(taps[:, length - 1 - d] * shifted(x, d).astype(jnp.float32)
               for d in range(length))


@jax.custom_vjp
def conv_silu(x, taps, bias=None):
    """`silu` of the causal depthwise convolution of `x` [B, S, w] with
    `taps` [w, L], plus `bias` [w] where one is given (`ssm.Mamba2Mixer`'s;
    the gated delta rule's has none, and its program is the one without),
    in float32, rounded once to `x.dtype`: one pass over `x` forward. The
    backward pass keeps `x`, the taps and the bias, computes the sum again,
    and is one pass over `x` and the cotangent."""
    pre = _taps_sum(x, taps)
    return jax.nn.silu(pre if bias is None else pre + bias).astype(x.dtype)


def _conv_silu_fwd(x, taps, bias=None):
    return conv_silu(x, taps, bias), (x, taps, bias)


def _conv_silu_bwd(res, g):
    x, taps, bias = res
    length = taps.shape[1]

    def d_pre(ahead: int):
        """The sum's cotangent as seen `ahead` positions AHEAD, from `x` and
        `g` shifted themselves: XLA fuses a shifted argument into the pass
        that reads it and writes a shifted intermediate out in float32
        first (`lfm2_moe.gated_taps`; 537 MB here)."""
        pre = sum(taps[:, length - 1 - d]
                  * shifted(x, d - ahead).astype(jnp.float32)
                  for d in range(length))
        if bias is not None:
            pre = pre + bias
        share = jax.nn.sigmoid(pre)
        return (shifted(g, -ahead).astype(jnp.float32) * share
                * (1.0 + pre * (1.0 - share)))

    # a token's cotangent comes from every later position that saw it
    ahead = [d_pre(d) for d in range(length)]
    dx = sum(taps[:, length - 1 - d] * ahead[d] for d in range(length))
    d_taps = jnp.stack(
        [jnp.sum(ahead[0] * shifted(x, length - 1 - j).astype(jnp.float32),
                 axis=(0, 1)) for j in range(length)], axis=-1)
    d_bias = (None if bias is None
              else jnp.sum(ahead[0], axis=(0, 1)).astype(bias.dtype))
    return dx.astype(x.dtype), d_taps.astype(taps.dtype), d_bias


conv_silu.defvjp(_conv_silu_fwd, _conv_silu_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def normed_gate(o, z, scale, eps: float):
    """`o / rms(o) * scale * silu(z)` over the last axis (a head's output)
    in float32, rounded once to `z.dtype`: one pass forward (reads `o` and
    `z`, writes the product), one backward (reads both and the cotangent,
    writes two cotangents). It keeps `o`, `z` and the scale."""
    n, gate = _normed_and_gate(o, z, eps)
    return (n * scale * gate).astype(z.dtype)


def _normed_and_gate(o, z, eps):
    o = o.astype(jnp.float32)
    n = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return n, jax.nn.silu(z.astype(jnp.float32))


def _normed_gate_fwd(o, z, scale, eps):
    return normed_gate(o, z, scale, eps), (o, z, scale)


def _normed_gate_bwd(eps, res, g):
    o, z, scale = res
    g = g.astype(jnp.float32)
    z32, o32 = z.astype(jnp.float32), o.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(o32 * o32, axis=-1, keepdims=True) + eps)
    n, share = o32 * inv, jax.nn.sigmoid(z32)
    gate = z32 * share
    d_n = g * scale * gate
    d_o = inv * (d_n - n * jnp.mean(d_n * n, axis=-1, keepdims=True))
    d_z = g * n * scale * (share * (1.0 + z32 * (1.0 - share)))
    d_scale = jnp.sum(g * n * gate, axis=tuple(range(g.ndim - 1)))
    return (d_o.astype(o.dtype), d_z.astype(z.dtype),
            d_scale.astype(scale.dtype))


normed_gate.defvjp(_normed_gate_fwd, _normed_gate_bwd)


def l2_normed(x):
    """`x / sqrt(sum(x^2) + 1e-6)` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _L2_EPS)


def log_uniform(top: float):
    """An initialiser: the log of a draw from uniform(0, `top`)."""
    def init(key, shape, dtype=jnp.float32):
        return jnp.log(jax.random.uniform(
            key, shape, dtype, minval=jnp.finfo(dtype).tiny, maxval=top))
    return init


class GatedDeltaNet(nn.Module):
    """x [B, S, hidden] -> (the mixer's output [B, S, hidden], its
    counters). `num_k_heads` key heads of `head_k_dim`, each serving
    `num_v_heads / num_k_heads` value heads of `head_v_dim` (neighbours
    together). `kernels`: whether the Pallas kernels run where the shape
    lets them (`delta_rule.takes` and `delta_prologue.takes`: heads of whole
    128-lane blocks, whole chunks), the prologue's and the rule's together
    or neither; the module's head says which pass runs and keeps what."""
    num_k_heads: int
    num_v_heads: int
    head_k_dim: int
    head_v_dim: int
    conv_taps: int
    eps: float
    dtype: Any
    kernels: Optional[bool] = None  # None: where the backend is a TPU

    @nn.compact
    def __call__(self, x):
        b, s, hidden = x.shape
        hk, hv = self.num_k_heads, self.num_v_heads
        dk, dv = self.head_k_dim, self.head_v_dim
        keys, values = hk * dk, hv * dv

        def dense(name, width):
            return nn.Dense(width, use_bias=False, dtype=self.dtype,
                            kernel_init=INIT, name=name)

        with jax.named_scope("linear_attn"):
            with jax.named_scope("gdn_in_proj"):
                qkvz = dense("in_proj_qkvz", 2 * keys + 2 * values)(x)
                ba = dense("in_proj_ba", 2 * hv)(x).astype(jnp.float32)
            taps = self.param("conv_taps", INIT,
                              (2 * keys + values, self.conv_taps),
                              jnp.float32)
            kernels = (use_kernels(self.kernels)
                       and delta_rule.takes((b, s, hk, dk), (b, s, hv, dv))
                       and delta_prologue.takes(s, dk, dv, self.conv_taps))
            with jax.named_scope("gdn_conv"):
                if kernels:
                    q, k, v = delta_prologue.conv_norm(qkvz, taps, keys, dk)
                else:
                    qkv = lax.optimization_barrier(conv_silu(
                        lax.optimization_barrier(
                            qkvz[..., :2 * keys + values]), taps))
            z = qkvz[..., 2 * keys + values:].reshape(b, s, hv, dv)
            with jax.named_scope("gdn_rule"):
                a_log = self.param("A_log", log_uniform(16.0), (hv,),
                                   jnp.float32)
                dt_bias = self.param("dt_bias", nn.initializers.ones, (hv,),
                                     jnp.float32)
                beta = jax.nn.sigmoid(ba[..., :hv])
                g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., hv:] + dt_bias)
                if kernels:
                    o, state = delta_rule.gated_delta_rule(q, k, v, g, beta,
                                                         dk)
                    o = o.reshape(b, s, hv, dv)
                else:
                    q = (l2_normed(qkv[..., :keys].reshape(b, s, hk, dk))
                         * dk ** -0.5).astype(self.dtype)
                    k = l2_normed(qkv[..., keys:2 * keys].reshape(
                        b, s, hk, dk)).astype(self.dtype)
                    v = qkv[..., 2 * keys:].reshape(b, s, hv, dv)
                    o, state = chunked_rule(q, k, v, g, beta, CHUNK,
                                            self.dtype)
                g, beta, state = (lax.stop_gradient(a)
                                  for a in (g, beta, state))
                counters = {
                    "gdn_decay_mean": jnp.mean(jnp.exp(g)),
                    "gdn_beta_mean": jnp.mean(beta),
                    "gdn_state_rms": jnp.sqrt(jnp.mean(state * state))}
            with jax.named_scope("gdn_norm_gate"):
                scale = self.param("norm_scale", nn.initializers.ones, (dv,),
                                   jnp.float32)
                y = lax.optimization_barrier(normed_gate(
                    *lax.optimization_barrier((o, z)), scale, self.eps))
            with jax.named_scope("gdn_out_proj"):
                out = dense("out_proj", hidden)(y.reshape(b, s, values))
        return out, counters
