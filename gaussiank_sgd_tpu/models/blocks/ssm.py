"""A state-space mixer: Mamba-2 (Dao and Gu, "Transformers are SSMs",
arXiv:2405.21060), as transformers' `Mamba2Mixer.torch_forward` lays it out
(`models/mamba2/modeling_mamba2.py`; the grouped gated norm is
`models/zamba2/modeling_zamba2.py`'s `Zamba2RMSNormGated`).

A head keeps a matrix `S` [state size, head size], zero before the sequence,
float32. Token t decays it by ONE number a head, `exp(dt_t A)` (`A < 0`, `dt_t
> 0`), writes `dt_t B_t (x) x_t` into it and reads it under `C_t`:

    S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T
    y_t = S_t^T C_t + D x_t

`B_t` and `C_t` [state size] are shared by the heads of a group (`heads /
groups` neighbours), `x_t` [head size], `dt_t`, `A` and `D` are a head's own.
There is no delta term and the decay is a scalar a head: `delta.py`'s chunk
algebra (a triangular system a chunk) and `ops/delta_rule.py`'s kernels do
not compute this rule. `recurrent_scan` is the sentence above, token by
token: what the CPU tests hold everything else to.

`chunked_scan` computes the same in chunks of `chunk` tokens (the state-space
duality's blocks), never a `[S, S]` matrix and no sequential step but one
elementwise update of the state a chunk. With `a_t = dt_t A`, `G` its running
sum within a chunk (only differences that are <= 0 are ever exponentiated: no
overflow at any `dt`):

    M[i, j] = (C_i . B_j) exp(G_i - G_j) dt_j   for j <= i    within a chunk
    local   = B^T (x * dt exp(G_last - G))      what the chunk writes
    S_c     = exp(G_last) S_{c-1} + local       a `lax.scan` over the chunks
    Y       = M x + exp(G) (C S_{c-1})

`C B^T` is one product a GROUP; the decays sit on `M` and on the head's side
(`x`, `y`: a head's 64 columns), so `B` and `C` enter every product as they
are, shared by their group's heads. The products take their operands in
`dtype` (the model's compute dtype) and accumulate in float32; running sums,
decays and the state are float32.

Its backward pass is JAX's own transpose of that, a group of heads at a time:
`chunked_scan` is a `lax.map` over the groups of a `jax.checkpoint`ed body,
so the backward pass holds one group's factors (`M` is `[B, chunks, heads a
group, chunk, chunk]`) and of the forward pass only what the scan reads. All
of it lives only while the one block that is being differentiated is
recomputed (`attention.recomputed`).

**Which form runs where.** On a TPU (`kernels` None) or with `kernels` true,
at the published chunk of 128 and a shape `ops/ssd_scan.takes` admits (whole
chunks; a group's heads x head size and the state size whole 128-lane tiles:
8 heads of 64 and 128 here), the mixer's scan is that file's three Pallas
kernels, `ssd_fwd`, `ssd_fwd_kept` and `ssd_bwd`: the same algebra with the
same roundings, a group's states in VMEM from a sequence's first chunk to its
last, `[x | B | C]` read where the convolution left it. Every other shape,
every other chunk and every CPU run is `chunked_scan`, the statement the
kernels are held to (`tests/test_ssd_kernels.py`, under Pallas' interpreter).

`Mamba2Mixer` is the mixer whole: one product to `[z | x B C | dt]`, a
depthwise causal convolution of a few taps WITH a bias and SiLU over `x B C`
(`delta.conv_silu`, which this mixer shares with the gated delta rule's; its
Pallas form, `ops/delta_prologue.py`, also norms q and k by head and is not
taken here), `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)`, the scan, then
`y silu(z)` normed by RMS over each GROUP's columns (`gated_group_norm`: gate
first, norm second, all of a group's heads under one root mean square;
`delta.normed_gate` norms a head first and gates second) and the output
product.

Device scopes: `ssm` around the mixer; inside it `ssm_in_proj`, `ssm_conv`,
`ssm_scan` (the softplus, the chunks and the state's scan: the kernels'
calls or `chunked_scan`'s loops, and the `D x` term), `ssm_norm_gate`,
`ssm_out_proj`. `ssm_conv` and `ssm_norm_gate` are passes of their own
(`optimization_barrier`): left alone XLA runs them inside the products beside
them, under those products' names. Counters (the module's second output):
`ssm_dt_mean`, `ssm_decay_mean` (mean of `exp(dt A)`), `ssm_state_rms` (root
mean square of the state after the last token).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from ...ops import ssd_scan
from .common import INIT, scaled_init, use_kernels
from .delta import conv_silu

CHUNK = 128             # tokens to a chunk (the published `chunk_size`)
# `dt_bias` at init is the inverse softplus of a draw uniform in its logarithm
# between the first two, no smaller than the third: Mamba-2's own defaults
# and the published config's `time_step_min`, `_max` and `_floor`
DT_MIN, DT_MAX, DT_FLOOR = 0.001, 0.1, 1e-4
_HIGHEST = lax.Precision.HIGHEST


def recurrent_scan(x, dt, a, b_in, c_in):
    """The recurrence token by token. x [B, T, H, P], dt [B, T, H] (> 0), a
    [H] (< 0), b_in and c_in [B, T, G, N] (group g serves heads `g * H / G`
    up to the next group's first); everything float32. Returns (y [B, T, H,
    P] WITHOUT the `D x` term, the state after the last token [B, H, N,
    P])."""
    b, _, h, p = x.shape
    n = b_in.shape[-1]
    b_in, c_in = (jnp.repeat(v, h // v.shape[2], axis=2)
                  for v in (b_in, c_in))

    def step(state, token):
        x_t, dt_t, b_t, c_t = token
        state = (state * jnp.exp(dt_t * a)[..., None, None]
                 + (dt_t[..., None] * b_t)[..., :, None] * x_t[..., None, :])
        return state, jnp.einsum("bhnp,bhn->bhp", state, c_t,
                                 precision=_HIGHEST)

    tokens = [jnp.moveaxis(v.astype(jnp.float32), 1, 0)
              for v in (x, dt, b_in, c_in)]
    state, y = lax.scan(step, jnp.zeros((b, h, n, p), jnp.float32), tokens)
    return jnp.moveaxis(y, 0, 1), state


def _product(dtype, spec, x, y):
    """`einsum` of operands held as `dtype`, summed in float32 (as
    `delta.py`'s own: a private name does not cross files)."""
    return jnp.einsum(
        spec, x.astype(dtype), y.astype(dtype),
        precision=_HIGHEST if dtype == jnp.float32 else None,
        preferred_element_type=jnp.float32)


def _group_scan(dtype, x, dt, a, b_in, c_in):
    """One group's heads, already in chunks. x [B, C, R, Q, P] (R heads a
    group, Q tokens a chunk), dt [B, C, R, Q] float32, a [R], b_in and c_in
    [B, C, Q, N]. Returns (y [B, C, R, Q, P] as `dtype`, the last state [B,
    R, N, P] float32)."""
    product = functools.partial(_product, dtype)
    q = x.shape[3]
    run = jnp.cumsum(dt * a[:, None], axis=-1)              # G [B, C, R, Q]
    lower = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(
        lower, run[..., :, None] - run[..., None, :], -jnp.inf))
    within = (product("bcin,bcjn->bcij", c_in, b_in)[:, :, None] * decay
              * dt[..., None, :])                           # M [B, C, R, Q, Q]
    last = run[..., -1:]
    written = x.astype(jnp.float32) * (dt * jnp.exp(last - run))[..., None]
    local = product("bcjn,bcrjp->bcrnp", b_in, written)

    def carry(state, chunk_of):
        kept, wrote = chunk_of
        return kept[..., None, None] * state + wrote, state

    state, incoming = lax.scan(
        carry, jnp.zeros(local.shape[:1] + local.shape[2:], jnp.float32),
        (jnp.moveaxis(jnp.exp(last[..., 0]), 1, 0),
         jnp.moveaxis(local, 1, 0)))
    y = (product("bcrij,bcrjp->bcrip", within, x)
         + product("bcin,bcrnp->bcrip", c_in, jnp.moveaxis(incoming, 0, 1))
         * jnp.exp(run)[..., None])
    return y.astype(dtype), state


def chunked_scan(x, dt, a, b_in, c_in, chunk: int = CHUNK,
                 dtype: Any = jnp.float32):
    """`recurrent_scan` in chunks of `chunk` tokens (the module's head has
    the algebra). x [B, T, H, P], dt [B, T, H] float32, a [H] float32, b_in
    and c_in [B, T, G, N]. The products' operands are `dtype`, their sums,
    the decays and the state float32, the output `dtype`. A sequence that is
    no whole number of chunks is filled up with tokens of `dt` 0, which
    neither decay nor write. The groups go one after the other (`lax.map`),
    each recomputed in the backward pass (`jax.checkpoint`). Returns (y [B,
    T, H, P] as `dtype`, the state after the last token [B, H, N, P]
    float32)."""
    b, t, h, p = x.shape
    g, n = b_in.shape[2:]
    r = h // g
    fill = -t % chunk
    c = (t + fill) // chunk

    def chunks(v, heads: bool):
        """[B, T, G * R, ...] -> [G, B, C, R, Q, ...] (a head's), or [B, T,
        G, N] -> [G, B, C, Q, N] (a group's)."""
        v = jnp.pad(v, ((0, 0), (0, fill)) + ((0, 0),) * (v.ndim - 2))
        if not heads:
            return jnp.moveaxis(v.reshape(b, c, chunk, g, n), 3, 0)
        v = v.reshape(b, c, chunk, g, r, *v.shape[3:])
        return jnp.moveaxis(v, (3, 4), (0, 3))

    y, state = lax.map(
        lambda of: jax.checkpoint(functools.partial(_group_scan, dtype))(*of),
        (chunks(x, True), chunks(dt.astype(jnp.float32), True),
         a.astype(jnp.float32).reshape(g, r), chunks(b_in, False),
         chunks(c_in, False)))
    # [G, B, C, R, Q, P] -> [B, C, Q, G, R, P]
    y = jnp.moveaxis(y, (0, 3), (3, 4)).reshape(b, t + fill, h, p)
    return y[:, :t], jnp.moveaxis(state, 0, 1).reshape(b, h, n, p)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def gated_group_norm(y, z, scale, groups: int, eps: float):
    """`v / rms_group(v) * scale` with `v = y silu(z)` over the last axis
    in `groups` equal runs of columns, each under its own root mean square,
    in float32, rounded once to `z.dtype`: one pass forward (reads `y` and
    `z`, writes the product), one backward (reads both and the cotangent,
    writes two cotangents). It keeps `y`, `z` and the scale."""
    n, _, _ = _gated_and_normed(y, z, groups, eps)
    return (n * scale).astype(z.dtype)


def _gated_and_normed(y, z, groups, eps):
    """(the normed product, what each run was divided by as its inverse,
    the product itself), float32; the last axis by run where it helps."""
    y32, z32 = y.astype(jnp.float32), z.astype(jnp.float32)
    v = (y32 * jax.nn.silu(z32)).reshape(*y.shape[:-1], groups, -1)
    inv = lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)
    return (v * inv).reshape(y.shape), inv, v


def _gated_group_norm_fwd(y, z, scale, groups, eps):
    return gated_group_norm(y, z, scale, groups, eps), (y, z, scale)


def _gated_group_norm_bwd(groups, eps, res, g):
    y, z, scale = res
    g = g.astype(jnp.float32)
    n, inv, _ = _gated_and_normed(y, z, groups, eps)
    by_run = n.reshape(*y.shape[:-1], groups, -1)
    d_n = (g * scale).reshape(by_run.shape)
    d_v = (inv * (d_n - by_run * jnp.mean(d_n * by_run, axis=-1,
                                          keepdims=True))).reshape(y.shape)
    y32, z32 = y.astype(jnp.float32), z.astype(jnp.float32)
    share = jax.nn.sigmoid(z32)
    d_y = d_v * z32 * share
    d_z = d_v * y32 * (share * (1.0 + z32 * (1.0 - share)))
    d_scale = jnp.sum(g * n, axis=tuple(range(g.ndim - 1)))
    return (d_y.astype(y.dtype), d_z.astype(z.dtype),
            d_scale.astype(scale.dtype))


gated_group_norm.defvjp(_gated_group_norm_fwd, _gated_group_norm_bwd)


def inverse_softplus_log_uniform(key, shape, dtype=jnp.float32):
    """An initialiser: `x` with `softplus(x)` a draw that is uniform in its
    logarithm between `DT_MIN` and `DT_MAX`, no smaller than `DT_FLOOR`
    (Mamba-2's `dt_bias`)."""
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        key, shape, dtype, math.log(DT_MIN), math.log(DT_MAX))), DT_FLOOR)
    return dt + jnp.log(-jnp.expm1(-dt))


def log_heads(key, shape, dtype=jnp.float32):
    """An initialiser: `log(1), log(2), ...` (Mamba-2's `A_log`)."""
    return jnp.log(jnp.arange(1, shape[0] + 1, dtype=dtype))


def uniform_taps(key, shape, dtype=jnp.float32):
    """An initialiser: uniform on `(-1, 1) / sqrt(taps)` (torch's own for a
    depthwise `Conv1d`, which Mamba-2 keeps)."""
    bound = shape[1] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


class Mamba2Mixer(nn.Module):
    """x [B, S, hidden] -> (the mixer's output [B, S, hidden], its
    counters). `num_heads` heads of `head_dim` (the inner width is their
    product, whatever `hidden` is), a state of `state_size` a head, `B` and
    `C` in `groups` groups. `out_init_scale`: what the output product's
    draw is multiplied by at init (`rescale_prenorm_residual`). `kernels`:
    whether the scan runs as `ops/ssd_scan.py`'s Pallas kernels where the
    shape lets them (`ssd_scan.takes`: whole chunks of 128, a group's heads
    and the state size whole 128-lane tiles); everywhere else
    `chunked_scan`."""
    num_heads: int
    head_dim: int
    state_size: int
    groups: int
    conv_taps: int
    eps: float
    dtype: Any
    chunk: int = CHUNK
    out_init_scale: float = 1.0
    kernels: Optional[bool] = None  # None: where the backend is a TPU

    @nn.compact
    def __call__(self, x):
        b, s, hidden = x.shape
        h, p, n, g = (self.num_heads, self.head_dim, self.state_size,
                      self.groups)
        inner, mixed = h * p, h * p + 2 * g * n

        with jax.named_scope("ssm"):
            with jax.named_scope("ssm_in_proj"):
                zxbcdt = nn.Dense(inner + mixed + h, use_bias=False,
                                  dtype=self.dtype, kernel_init=INIT,
                                  name="in_proj")(x)
            z = zxbcdt[..., :inner]
            taps = self.param("conv_taps", uniform_taps,
                              (mixed, self.conv_taps), jnp.float32)
            bias = self.param("conv_bias", nn.initializers.zeros, (mixed,),
                              jnp.float32)
            with jax.named_scope("ssm_conv"):
                xbc = lax.optimization_barrier(conv_silu(
                    lax.optimization_barrier(
                        zxbcdt[..., inner:inner + mixed]), taps, bias))
            with jax.named_scope("ssm_scan"):
                a_log = self.param("A_log", log_heads, (h,), jnp.float32)
                dt_bias = self.param("dt_bias", inverse_softplus_log_uniform,
                                     (h,), jnp.float32)
                skip = self.param("D", nn.initializers.ones, (h,),
                                  jnp.float32)
                dt = jax.nn.softplus(
                    zxbcdt[..., inner + mixed:].astype(jnp.float32) + dt_bias)
                a = -jnp.exp(a_log)
                if (use_kernels(self.kernels) and self.chunk == ssd_scan.CHUNK
                        and ssd_scan.takes((b, s, h, p), (b, s, g, n))):
                    # heads side by side all the way: a head's 64 columns
                    # as a minor axis of their own would be half a tile
                    y, state = ssd_scan.ssd_scan(xbc, dt, a, g, n)
                    y = (y.astype(jnp.float32) + jnp.repeat(skip, p)
                         * xbc[..., :inner].astype(jnp.float32))
                else:
                    x_in = xbc[..., :inner].reshape(b, s, h, p)
                    y, state = chunked_scan(
                        x_in, dt, a,
                        xbc[..., inner:inner + g * n].reshape(b, s, g, n),
                        xbc[..., inner + g * n:].reshape(b, s, g, n),
                        self.chunk, self.dtype)
                    y = (y.astype(jnp.float32) + skip[:, None]
                         * x_in.astype(jnp.float32))
                y = y.astype(self.dtype).reshape(b, s, inner)
                dt, state = lax.stop_gradient(dt), lax.stop_gradient(state)
                counters = {
                    "ssm_dt_mean": jnp.mean(dt),
                    "ssm_decay_mean": jnp.mean(
                        jnp.exp(dt * lax.stop_gradient(a))),
                    "ssm_state_rms": jnp.sqrt(jnp.mean(state * state))}
            with jax.named_scope("ssm_norm_gate"):
                scale = self.param("norm_scale", nn.initializers.ones,
                                   (inner,), jnp.float32)
                y = lax.optimization_barrier(gated_group_norm(
                    *lax.optimization_barrier((y, z)),
                    scale, g, self.eps))
            with jax.named_scope("ssm_out_proj"):
                out = nn.Dense(
                    hidden, use_bias=False, dtype=self.dtype,
                    kernel_init=scaled_init(self.out_init_scale),
                    name="out_proj")(y)
        return out, counters
