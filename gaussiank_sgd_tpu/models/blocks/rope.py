"""How positions turn q and k: the pairs' frequencies (plain and
yarn-scaled), and the rotary turn whole under the device scope `rope`: one
fused pass over q and one over k (the pairs' exchange by a 0/1 product, the
turn in float32, the attention's scale, the one rounding) against cos and
sin tables that `rope_table` makes once on the host."""

from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


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
               interleave: bool, width: int, lead: bool = False):
    """cos and signed sin times `scale`, float32 [positions, width], laid
    out at the width of the axis they turn: made ONCE for each (positions,
    frequencies, scale, layout) a process meets, on the host, and constants
    of every program that uses them (never computed on the device, so never
    inside a fusion that visits every head). The angle is float32 position
    times float32 frequency. Half-split `[cos | cos]` and `[-sin | sin]`;
    adjacent pairs each value twice, the sine's sign alternating; 1 and 0
    in the lanes before the turned part (after it with `lead`)."""
    ang = (np.arange(positions, dtype=np.float32)[:, None]
           * np.asarray(inv_freq, np.float32)[None, :]).astype(np.float64)
    cos, sin = np.cos(ang) * scale, np.sin(ang) * scale
    if interleave:
        cos = np.repeat(cos, 2, axis=-1)
        sin = np.stack([-sin, sin], axis=-1).reshape(positions, -1)
    else:
        cos, sin = np.tile(cos, 2), np.concatenate([-sin, sin], axis=-1)
    rest = width - cos.shape[-1]
    still = ((0, 0), (0, rest) if lead else (rest, 0))
    return (np.pad(cos, still, constant_values=1.0).astype(np.float32),
            np.pad(sin, still).astype(np.float32))


def _partner_matrix(width: int, rot: int, interleave: bool,
                    lead: bool = False) -> np.ndarray:
    """0/1 [width, width]: `x @ m` holds at every lane of the turned part
    (the last `rot`, the first with `lead`) the other entry of that lane's
    pair, and 0 outside it."""
    place = np.arange(rot)
    other = place ^ 1 if interleave else (place + rot // 2) % rot
    m = np.zeros((width, width), np.float32)
    first = 0 if lead else width - rot
    m[first + other, first + place] = 1.0
    return m


class _Turn(NamedTuple):
    """How `_turned` turns: the width of the turned part, the pairs'
    layout, whether it turns BACK (the sine's sign: the cotangent's turn),
    the factor and dtype of what it hands out, the dtype of its cotangent,
    whether the turned part is the head's first entries and not its last."""
    rot: int
    interleave: bool
    back: bool
    out_scale: float
    dtype: Any
    cotangent_dtype: Any
    lead: bool = False


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _turned(how: _Turn, x, cos, sin):
    """`(x * cos + partner(x) * sin) * out_scale` in float32, rounded once
    to `how.dtype`; the lanes outside the turned part pass through (a
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
        jnp.asarray(_partner_matrix(width, how.rot, how.interleave, how.lead),
                    x.dtype),
        precision=exact, preferred_element_type=jnp.float32)
    x = x.astype(jnp.float32)
    straight = x * cos[None, :, None, :]
    across = partner * sin[None, :, None, :]
    y = straight - across if how.back else straight + across
    if width > how.rot:
        lane = lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
        y = jnp.where(lane < how.rot if how.lead else lane >= width - how.rot,
                      y, x)
    return (y * how.out_scale).astype(how.dtype)


def _turned_fwd(how, x, cos, sin):
    return _turned(how, x, cos, sin), (cos, sin)


def _turned_bwd(how, tables, g):
    back = how._replace(back=not how.back, dtype=how.cotangent_dtype,
                        cotangent_dtype=how.dtype)
    return _turned(back, g, *tables), None, None


_turned.defvjp(_turned_fwd, _turned_bwd)


def apply_rope(x, inv_freq, scale: float = 1.0, interleave: bool = False,
               out_scale: float = 1.0, dtype: Any = jnp.float32,
               lead: bool = False):
    """Rotate the last `2 * len(inv_freq)` entries of `x` [B, S, H, W] (with
    `lead` the FIRST: a `partial_rotary_factor`) by their position
    (`inv_freq`: the pairs' frequencies, any sequence); what lies outside
    them passes through. Pair i of the D turned entries is (x[i],
    x[i + D/2]), or with `interleave` the adjacent (x[2i], x[2i + 1]),
    turned in place; cos and sin times `scale` (yarn's `attention_factor`).
    In float32; the result times `out_scale` (the attention's 1 / sqrt(d)),
    rounded once to `dtype`. One pass over `x` against `rope_table`'s
    constants (`_turned`)."""
    with jax.named_scope("rope"):
        cos, sin = rope_table(
            x.shape[1], tuple(np.asarray(inv_freq, np.float64).tolist()),
            float(scale), bool(interleave), x.shape[-1], bool(lead))
        how = _Turn(2 * len(inv_freq), bool(interleave), False,
                    float(out_scale), jnp.dtype(dtype), x.dtype, bool(lead))
        return _turned(how, x, cos, sin)
