"""How tokens reach their experts and come back.

**One chip's share of an expert group.** The layer is told which experts it
holds, `(share, shares)`: experts `share * E / shares` up to the next
share's first. It routes over all E and adds only its own experts' terms;
what the absent experts would add is left out and the partial result goes
on (on one chip there is no exchange, and nothing stands in for one).

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

**Two forms of an expert**, chosen by the model's code (`Experts.form`,
`GatedMLP.form`): `GATED`, `W2 (silu(W1 x) * W3 x)`, three matrices an expert and
three grouped products a layer; `RELU2`, `W2 relu(W1 x)^2`, two of each and
no leaf `w3` (the routed experts and the shared one alike)."""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from ...ops import grouped_matmul
from .common import INIT, scaled_init, use_kernels

# the slots that a block of tokens has for its live rows in `_summed`
_ROOM = 512
GATED, RELU2 = "gated", "relu2"     # an expert's form: the module's head


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
    the first `cap` sorted assignments, which hold every live one. `w3`
    None: the experts are `RELU2` ones."""
    first = order[:cap]
    live = inverse < jnp.sum(sizes)
    rows = to_rows(x, first, inverse, live, top)
    gate = grouped_product(rows, w1, sizes, kernels)
    if w3 is None:                  # `RELU2`: no second product going in
        with jax.named_scope("moe_gate"):
            gated = jnp.square(jax.nn.relu(gate))
    else:
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
    """`W2 (silu(W1 x) * W3 x)` on the last axis (`form` `RELU2`: `W2
    relu(W1 x)^2`, no leaf `w3`), under the device scope `device_scope`:
    float32 parameters multiplied as `x.dtype`."""
    width: int
    device_scope: str
    form: str = GATED
    out_init_scale: float = 1.0     # `w2`'s draw at init times this

    @nn.compact
    def __call__(self, x):
        wide = (x.shape[-1], self.width)
        out_init = scaled_init(self.out_init_scale)
        w1 = self.param("w1", INIT, wide, jnp.float32).astype(x.dtype)
        if self.form == RELU2:
            w2 = self.param("w2", out_init, wide[::-1], jnp.float32
                            ).astype(x.dtype)
            with jax.named_scope(self.device_scope):
                return jnp.dot(jnp.square(jax.nn.relu(jnp.dot(x, w1))), w2)
        w3 = self.param("w3", INIT, wide, jnp.float32).astype(x.dtype)
        w2 = self.param("w2", out_init, wide[::-1], jnp.float32
                        ).astype(x.dtype)
        with jax.named_scope(self.device_scope):
            return jnp.dot(jax.nn.silu(jnp.dot(x, w1)) * jnp.dot(x, w3), w2)


class Experts(nn.Module):
    """The router over all `num_experts` and the experts held here, of the
    form `form` (the module's head; the shared expert's too).
    `scoring` `softmax`: probabilities over all experts; `sigmoid`: each
    logit's own. With `select_bias` a leaf `router_bias` is added to the
    scores where the `experts_per_token` are CHOSEN and nowhere else (its
    gradient is exactly zero: a selection has none). The chosen scores are
    renormalised (their sum plus `sum_eps` the divisor) and multiplied by
    `scale`. `shared_width` > 0: a gated expert of that width that every
    token passes, added by every share; with `shared_gate` times
    `sigmoid(x w_g)`, one number a token (`w_g` the leaf `shared_gate`
    [hidden], float32; under the scope `moe_shared`), whose mean is the
    counter `moe_shared_gate_mean`."""
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
    shared_gate: bool = False
    form: str = GATED
    out_init_scale: float = 1.0     # the draws of `w2`, the held experts'
    # and the shared one's, at init times this

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
        if self.form not in (GATED, RELU2):
            raise ValueError(f"form {self.form!r}")
        held = self.num_experts // self.shares
        x = x.reshape(tokens, hidden)
        with jax.named_scope("moe_router"):
            # float32 throughout: a near tie decides which expert is paid
            router = self.param("router", INIT, (hidden, self.num_experts),
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
        w1 = self.param("w1", INIT, shape, jnp.float32)
        w3 = (None if self.form == RELU2
              else self.param("w3", INIT, shape, jnp.float32))
        w2 = self.param("w2", scaled_init(self.out_init_scale),
                        (held, self.width, hidden), jnp.float32)
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
        shared_counters = {}
        if self.shared_width:
            shared = GatedMLP(self.shared_width, "moe_shared", self.form,
                              self.out_init_scale,
                              name="shared")(x).astype(jnp.float32)
            if self.shared_gate:
                with jax.named_scope("moe_shared"):
                    w_g = self.param("shared_gate", INIT, (hidden,),
                                     jnp.float32)
                    share = jax.nn.sigmoid(jnp.sum(
                        x.astype(jnp.float32) * w_g, axis=-1, keepdims=True))
                    shared = shared * share
                shared_counters["moe_shared_gate_mean"] = jnp.mean(
                    lax.stop_gradient(share))
            y = y + shared
        load = sizes.astype(jnp.float32)
        counters = {
            "moe_held_assignments": jnp.sum(load),
            "moe_room_used": room_used(enough, top, inverse, sizes),
            "moe_load_max_over_mean": jnp.max(load) / jnp.maximum(
                jnp.mean(load), 1.0),
            "moe_tokens_unserved": 1.0 - jnp.mean(served.astype(jnp.float32)),
            **shared_counters}
        return y.astype(self.dtype).reshape(b, s, hidden), counters


def model_counters(per_layer):
    """The model's counters from its expert layers': the assignments held
    summed, the room used and the load of the worst layer, the unserved
    share's mean and, where the shared expert is gated, its gate's."""
    stacked = jax.tree.map(lambda *v: jnp.stack(v), *per_layer)
    gate = ({"moe_shared_gate_mean": jnp.mean(stacked["moe_shared_gate_mean"])}
            if "moe_shared_gate_mean" in stacked else {})
    return {
        **gate,
        "moe_held_assignments": jnp.sum(stacked["moe_held_assignments"]),
        "moe_room_used": jnp.max(stacked["moe_room_used"]),
        "moe_load_max_over_mean": jnp.max(
            stacked["moe_load_max_over_mean"]),
        "moe_tokens_unserved": jnp.mean(stacked["moe_tokens_unserved"])}
