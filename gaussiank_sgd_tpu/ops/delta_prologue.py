"""What lies between the gated delta-rule mixer's input product and the
rule's kernels, as one Pallas TPU pass each way (PR 47): the causal depthwise
convolution of a few taps with SiLU over `[q | k | v]` and the L2 norms of q
and k, which `models/blocks/delta.py` states as `conv_silu` followed by
`l2_normed` and the scale.

    conv_norm(qkvz, taps, keys, dk)  ->  (q, k, v)

`qkvz` [B, S, >= 2 keys + values] in the model's compute dtype, of which the
first `2 keys + values` columns are read WHERE THEY LIE (a block of columns by
the index map: no slice is written); `taps` [2 keys + values, L] float32. The
kernels compute, an entry, `pre = sum_j taps[:, j] x_{t - (L - 1 - j)}` in
float32 (zeros before a sequence's first position), `y = silu(pre)` rounded to
`qkvz.dtype` where `conv_silu` rounds, and for the columns of q and k
`y * rsqrt(sum y^2 + 1e-6)` over each head's `dk` lanes in float32 (q times
`dk ** -0.5`), rounded once more as the mixer does. They hand q and k
[B, S, keys] and v [B, S, values] out as `ops/delta_rule.py`'s kernels read
them: no four-dimensional array, no relayout between the two.

**The grid** runs over (batch, blocks of positions). A step holds `(rows,
2 keys + values)` of `qkvz` and, from a second view of the same array, the
one tile of sixteen positions before its block (its last `L - 1` are the
halo; zeros at a sequence's first block). It goes through the heads in a
loop of the program: a head's columns are turned to float32 once into a
scratch `[8 + rows, head]`, whose rows at 8 - d are the head as seen d
positions back, so a shift along the sequence is a sublane offset of a load.
The sum is computed once an entry.

**Two calls, a name each** (a device trace shows them under the mixer's scope
`gdn_conv`):

  gdn_conv_fwd  q, k and v; keeps nothing. Both forward passes of a
                recomputed layer are this call.
  gdn_conv_bwd  reads the same columns of `qkvz`, the taps and the three
                cotangents, computes the sum, the sigmoid and the normed
                heads once more from x (`qkvz` and the taps are all the
                backward pass keeps: no float32 copy of q or k), takes dq and
                dk back through the norm in registers, forms the sum's
                cotangent `dpre` once and writes `dx[t] = sum_d taps[:, L - 1
                - d] dpre[t + d]`. The blocks of a sequence run LAST TO
                FIRST (the position axis is sequential): a block needs
                `dpre` at the `L - 1` positions after it, which the step
                before left in a scratch (zeros after a sequence's last
                position), and x at the `L - 1` positions before it, which
                the one-tile view gives. The taps' gradient is summed in
                float32 in the call's own output block, eight partial sums
                (a vreg's sublanes) a tap and batch row, which XLA adds up:
                `[B, 8 L, 2 keys + values]` float32, 2 MB.

`defvjp(optimize_remat=True)` is not used: `ops/delta_rule.py`'s head says
what it does to a kernel's `op_name`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

L2_EPS = 1e-6           # beside the squares' sum (`blocks/delta.l2_normed`)
_LANES = 128
_TILE = 16              # positions to the view before a block (a bf16 tile)
_SUBLANES = 8           # rows of the halo as float32 (one vreg)
_MOST_ROWS = 256        # a grid step's positions at most
_CHUNK = 32             # rows a statement of the kernels' bodies goes over
_F32 = jnp.float32


def rows_a_step(positions: int) -> int:
    """How many positions a grid step holds: the largest divisor of the
    sequence up to 256 that is whole tiles of sixteen (0: none)."""
    return next((n for n in range(min(positions, _MOST_ROWS), 0, -1)
                 if positions % n == 0 and n % _TILE == 0), 0)


def takes(positions: int, dk: int, dv: int, length: int) -> bool:
    """Whether the kernels take the shape: heads of whole 128-lane blocks
    (a head of q or k is normed over its own lanes), a sequence that blocks
    of whole tiles divide, taps that reach back no further than a vreg."""
    return (dk % _LANES == 0 and dv % _LANES == 0
            and 1 <= length <= _SUBLANES + 1 and rows_a_step(positions) > 0)


def vmem_bytes(rows: int, width: int, itemsize: int, length: int,
               backward: bool) -> int:
    """VMEM of a call's blocks (each twice: the pipeline's two buffers) and
    its scratch, with room for what a head's statements spill."""
    block = itemsize * rows * width
    blocks = (3 if backward else 2) * block + itemsize * _TILE * width \
        + 4 * length * width
    scratch = 4 * (_SUBLANES + rows) * _LANES
    if backward:    # the taps' partial sums; the cotangent's rows and halo
        blocks += 4 * _SUBLANES * length * width
        scratch = 2 * scratch + 4 * _SUBLANES * width
    return 2 * blocks + scratch + (8 << 20)


def _sigmoid(x):
    # one transcendental and no division (Mosaic's `logistic` divides)
    return 0.5 * jnp.tanh(0.5 * x) + 0.5


def _chunks(rows: int):
    """(first row, rows) of the statements a head's block is cut into: whole
    vregs, few enough live at once."""
    size = next(n for n in range(min(rows, _CHUNK), 0, -1)
                if rows % n == 0 and n % _SUBLANES == 0)
    return [(r, size) for r in range(0, rows, size)]


def _sections(keys: int, values: int, dk: int):
    """(first column, heads, a head's width, normed, scale) of q, k and v;
    v, which no norm holds to its heads, a lane block at a time."""
    return ((0, keys // dk, dk, True, dk ** -0.5),
            (keys, keys // dk, dk, True, 1.0),
            (2 * keys, values // _LANES, _LANES, False, 1.0))


def _lanes(first: int, head, width: int):
    return pl.ds(pl.multiple_of(first + head * width, _LANES), width)


def _seen_back(xf_ref, x_ref, before_ref, cols, first_block):
    """A head's columns in float32 into `xf_ref` [8 + rows, head]: row 8 + t
    is position t of the block, the rows above it the positions before the
    block (zeros before a sequence's first)."""
    above = before_ref[_TILE - _SUBLANES:, cols].astype(_F32)
    xf_ref[:_SUBLANES, :] = jnp.where(first_block, 0.0, above)
    xf_ref[_SUBLANES:, :] = x_ref[:, cols].astype(_F32)


def _taps_sum(xf_ref, taps, r: int, size: int):
    """(the sum over the taps at rows `r` to `r + size`, the head as seen
    0 to L - 1 positions back there)."""
    length = taps.shape[0]
    seen = [xf_ref[_SUBLANES + r - d:_SUBLANES + r - d + size, :]
            for d in range(length)]
    pre = sum(taps[length - 1 - d:length - d, :] * seen[d]
              for d in range(length))
    return pre, seen


def _forward_kernel(sections, dtype, x_ref, before_ref, taps_ref, q_ref,
                    k_ref, v_ref, xf_ref):
    rows = x_ref.shape[0]
    first_block = pl.program_id(1) == 0

    for (first, heads, width, normed, scale), o_ref in zip(
            sections, (q_ref, k_ref, v_ref)):
        def head(h, _, first=first, width=width, normed=normed, scale=scale,
                 o_ref=o_ref):
            cols = _lanes(first, h, width)
            _seen_back(xf_ref.at[:, :width], x_ref, before_ref, cols,
                       first_block)
            taps = taps_ref[:, cols]
            for r, size in _chunks(rows):
                pre, _ = _taps_sum(xf_ref.at[:, :width], taps, r, size)
                y = (pre * _sigmoid(pre)).astype(dtype)
                if normed:
                    y = y.astype(_F32)
                    y = y * lax.rsqrt(
                        jnp.sum(y * y, axis=-1, keepdims=True) + L2_EPS)
                    y = (y * scale if scale != 1.0 else y).astype(dtype)
                o_ref[r:r + size, _lanes(0, h, width)] = y
        lax.fori_loop(0, heads, head, None)


def _backward_kernel(sections, dtype, x_ref, before_ref, taps_ref, dq_ref,
                     dk_ref, dv_ref, dx_ref, dtaps_ref, xf_ref, dp_ref,
                     after_ref):
    rows, length = x_ref.shape[0], taps_ref.shape[0]
    # the grid runs a sequence's blocks last to first
    first_block = pl.program_id(1) == pl.num_programs(1) - 1

    @pl.when(pl.program_id(1) == 0)
    def _():
        after_ref[...] = jnp.zeros(after_ref.shape, _F32)
        dtaps_ref[...] = jnp.zeros(dtaps_ref.shape, _F32)

    for (first, heads, width, normed, scale), g_ref in zip(
            sections, (dq_ref, dk_ref, dv_ref)):
        def head(h, _, first=first, width=width, normed=normed, scale=scale,
                 g_ref=g_ref):
            cols = _lanes(first, h, width)
            xf, dp = xf_ref.at[:, :width], dp_ref.at[:, :width]
            _seen_back(xf, x_ref, before_ref, cols, first_block)
            taps = taps_ref[:, cols]
            d_taps = [jnp.zeros((_SUBLANES, width), _F32)] * length
            for r, size in _chunks(rows):
                pre, seen = _taps_sum(xf, taps, r, size)
                share = _sigmoid(pre)
                g = g_ref[r:r + size, _lanes(0, h, width)].astype(_F32)
                if normed:
                    y = (pre * share).astype(dtype).astype(_F32)
                    inv = lax.rsqrt(
                        jnp.sum(y * y, axis=-1, keepdims=True) + L2_EPS)
                    n = y * inv
                    if scale != 1.0:
                        g = g * scale
                    g = inv * (g - n * jnp.sum(g * n, axis=-1,
                                               keepdims=True))
                d_pre = g * share * (1.0 + pre * (1.0 - share))
                dp[r:r + size, :] = d_pre
                for j in range(length):
                    # eight partial sums a column: whole vregs added
                    by = d_pre * seen[length - 1 - j]
                    d_taps[j] = d_taps[j] + sum(
                        by[i:i + _SUBLANES]
                        for i in range(0, size, _SUBLANES))
            # the positions after the block, from the step before; this
            # block's first ones for the step after
            dp[rows:, :] = after_ref[:, cols]
            after_ref[:, cols] = dp[:_SUBLANES, :]
            for j in range(length):
                at = pl.ds(_SUBLANES * j, _SUBLANES)
                dtaps_ref[at, cols] = dtaps_ref[at, cols] + d_taps[j]
            for r, size in _chunks(rows):
                dx = sum(taps[length - 1 - d:length - d, :]
                         * dp[r + d:r + d + size, :] for d in range(length))
                dx_ref[r:r + size, cols] = dx.astype(dtype)
        lax.fori_loop(0, heads, head, None)


def _call(qkvz, taps, keys: int, dk: int, cotangents, interpret: bool):
    """The forward call, or with `cotangents` (dq, dk, dv) the backward."""
    b, s, _ = qkvz.shape
    width, length = taps.shape
    values = width - 2 * keys
    rows = rows_a_step(s)
    blocks = s // rows
    sections = _sections(keys, values, dk)
    backward = cotangents is not None

    def at(block):
        return blocks - 1 - block if backward else block

    def before(i, block):
        return (i, jnp.maximum(at(block) * (rows // _TILE) - 1, 0), 0)

    def columns(n):
        return pl.BlockSpec((None, rows, n),
                            lambda i, block: (i, at(block), 0))
    in_specs = [columns(width),
                pl.BlockSpec((None, _TILE, width), before),
                pl.BlockSpec((length, width), lambda i, block: (0, 0))]
    heads = [columns(keys), columns(keys), columns(values)]
    scratch = [pltpu.VMEM((_SUBLANES + rows, dk), _F32)]
    if backward:
        kernel, name = _backward_kernel, "gdn_conv_bwd"
        in_specs += heads
        out_specs = [columns(width),
                     pl.BlockSpec((None, _SUBLANES * length, width),
                                  lambda i, block: (i, 0, 0))]
        out_shape = [
            jax.ShapeDtypeStruct((b, s, width), qkvz.dtype),
            jax.ShapeDtypeStruct((b, _SUBLANES * length, width), _F32)]
        scratch += [pltpu.VMEM((rows + _SUBLANES, dk), _F32),
                    pltpu.VMEM((_SUBLANES, width), _F32)]
    else:
        kernel, name = _forward_kernel, "gdn_conv_fwd"
        out_specs = heads
        out_shape = [jax.ShapeDtypeStruct((b, s, n), qkvz.dtype)
                     for n in (keys, keys, values)]
    entries = b * s * width
    return pl.pallas_call(
        functools.partial(kernel, sections, qkvz.dtype),
        name=name, interpret=interpret, out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(b, blocks), in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",
                                 "arbitrary" if backward else "parallel"),
            vmem_limit_bytes=vmem_bytes(rows, width, qkvz.dtype.itemsize,
                                        length, backward)),
        cost_estimate=pl.CostEstimate(
            flops=(40 if backward else 20) * entries,
            transcendentals=2 * entries,
            bytes_accessed=(3 if backward else 2) * entries
            * qkvz.dtype.itemsize))(
                qkvz, qkvz, taps.astype(_F32).T, *(cotangents or ()))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def conv_norm(qkvz, taps, keys: int, dk: int, interpret: bool = False):
    """q, k [B, S, keys] (normed over heads of `dk`, q scaled) and v [B, S,
    the rest of the taps' columns] from the first columns of `qkvz` (the
    module's head has the arithmetic). `interpret`: under Pallas'
    interpreter (the CPU tests)."""
    return tuple(_call(qkvz, taps, keys, dk, None, interpret))


def _conv_norm_fwd(qkvz, taps, keys, dk, interpret):
    return conv_norm(qkvz, taps, keys, dk, interpret), (qkvz, taps)


def _conv_norm_bwd(keys, dk, interpret, res, cotangents):
    qkvz, taps = res
    dx, d_taps = _call(qkvz, taps, keys, dk, cotangents, interpret)
    width, length = taps.shape
    d_taps = jnp.sum(d_taps.reshape(-1, length, _SUBLANES, width),
                     axis=(0, 2)).T
    rest = qkvz.shape[-1] - width
    return (jnp.pad(dx, ((0, 0), (0, 0), (0, rest))),
            d_taps.astype(taps.dtype))


conv_norm.defvjp(_conv_norm_fwd, _conv_norm_bwd)
