"""Mamba-2's chunked scan as Pallas TPU kernels (PR 49): what
`models/blocks/ssm.chunked_scan` states, forward and backward, with a GROUP's
states in VMEM from a sequence's first chunk to its last.

    ssd_scan(xbc, dt, a, groups, n)  ->  (y, the last token's state)

`xbc` [B, T, H x P + 2 G x N] in the products' dtype: x, B and C side by side
along the last axis, heads and groups side by side in each, as the mixer's
convolution leaves them (no four-dimensional array, no slice, no relayout, no
`repeat`: the kernels' index maps find a group's columns of each; group g
serves the `R = H / G` heads whose columns follow `g R P`), `dt` [B, T, H]
float32 (> 0), `a` [H] float32 (< 0), `n` the state size; y comes back `[B,
T, H x P]` WITHOUT the `D x` term, the state `[B, H, N, P]` float32. The
algebra of a chunk is `blocks/ssm.py`'s head; here ALL of it happens in VMEM:
the running sum `G` of `dt a`, the lower block of `exp(G_i - G_j)`, `C B^T`
once a group, `M`, `Y`, what the chunk writes and the state's update. The
products take their operands in `x.dtype`, rounded where `ssm._group_scan`
rounds them (`M`, the written `x dt exp(G_last - G)`, the incoming state, B,
C, x) and sum in float32 (float32 operands: at `highest`); running sums,
decays, the state, its cotangent and the kept states are float32.

**The grid** runs over (batch, group, blocks of chunks), the last sequential.
A step reads `(chunks x 128, R P)` of x and `(chunks x 128, N)` of B and C
where the mixer has them, the group's lane offset by the index map, and loops
over its chunks with the group's states, heads side by side as `[N, R P]`
float32 (256 KB at 8 heads of 64), in a VMEM scratch. `dt` arrives as `[B, G,
chunks, R, 128]` (4 MB a layer transposed by XLA: a token's step has to lie
along the lanes for a block to hold it), a chunk's `[R, 128]` one vreg at 8
heads. What a head's side needs down the ROWS of a chunk (the running sum as
a column along a `[128, 128]` tile, `exp(G)` and `dt exp(G_last - G)` down a
head's columns) the matrix unit makes from those rows, exactly: the float32
row's three bfloat16 pieces, one under the other, against a matrix of zeros
and ones three times under itself (`_Masks`), so no lane is broadcast or
reduced on the vector unit; the sums over a head's columns that the backward
pass needs a token a lane come the same way. A chunk's work goes a TILE of 128
columns at a time (two heads of 64 and their `[N, 128]` of the states: 16
vregs): `C S` and what the chunk writes (`B^T (x dt exp(..))`) are one product
a tile for its heads, and `M x` is each head's `M_h` times the tile's x with
the other heads' columns zeroed, summed (a head of 64 is half a tile). One
chunk a body of the loop: the eight heads' chains are independent, and what
binds the body is the matrix unit's occupancy (a product of 128 rows holds a
unit 128 cycles whatever its depth), not a chain's latency (PERF.md section
6, PR 49, has the schedule's counts).

**Three calls, a name each** (a device trace shows them; `scope_tree` books
them under `ssm_scan` by their `op_name` path):

  ssd_fwd       y and the final state; keeps nothing (what runs where
                nothing is differentiated)
  ssd_fwd_kept  the same and every chunk's incoming state, `[B, G, chunks, N,
                R P]` float32 (268 MB a layer at 2 x 8192 tokens), alive only
                while the block is differentiated. Both forward passes of a
                block under `jax.checkpoint` are this call, for the reason
                `ops/delta_rule.py`'s head gives: `defvjp(optimize_remat=
                True)` would strip the kernel's `op_name` of its path
  ssd_bwd       ONE kernel, the chunks in reverse with the states' cotangent
                in the scratch: a chunk's factors are computed again from its
                inputs and its kept state, and dx, ddt, da (a chunk's share, a
                token a lane; XLA sums them), dB and dC leave it, dB and dC
                summed over the group's heads before they are written.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128             # tokens to a chunk (`blocks/ssm.CHUNK` is this)
_LANES = 128
_MOST_CHUNKS = 8        # a grid step's chunks at most
_HIGHEST = lax.Precision.HIGHEST
_F32 = jnp.float32
_MASKED = -1e30         # the exponent of a decay above the diagonal
# dimension numbers of `lax.dot_general` for x @ y, x @ y.T and x.T @ y
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def chunks_a_step(chunks: int) -> int:
    """How many chunks one grid step goes through: the largest divisor of
    the sequence's `chunks` up to 8 (a chunk's blocks and kept states are
    about 1 MB of VMEM, twice for the pipeline's two buffers)."""
    return next(n for n in range(min(chunks, _MOST_CHUNKS), 0, -1)
                if chunks % n == 0)


def takes(x_shape, b_shape) -> bool:
    """Whether the kernels take the shape (x `[B, T, H, P]`, B and C `[B, T,
    G, N]`): a sequence of whole chunks, heads that divide into the groups, a
    group's heads x head size and the state size whole 128-lane tiles (and
    B's first column in `[x | B | C]` a whole number of its blocks), a head
    that divides a tile or is whole tiles."""
    (_, t, h, p), (_, _, g, n) = x_shape, b_shape
    return (t > 0 and t % CHUNK == 0 and h % g == 0
            and (h // g * p) % _LANES == 0 and n % _LANES == 0
            and (h * p) % n == 0
            and (_LANES % p == 0 or p % _LANES == 0))


def vmem_bytes(nb: int, r: int, p: int, n: int, itemsize: int,
               backward: bool) -> int:
    """VMEM of a call's blocks (each twice: the pipeline's two buffers), its
    scratch and what a chunk's factors take beside the registers (a generous
    dozen `[128, 128]` float32 a head and two dozen `[128, R P]` a group)."""
    rows, wide = nb * CHUNK, r * p
    state = 4 * n * wide
    given = itemsize * rows * (wide + 2 * n) + 4 * nb * max(r, 8) * CHUNK
    blocks = given + itemsize * rows * wide + (nb + 1) * state
    if backward:
        blocks = (2 * given + itemsize * rows * wide + (nb + 2) * state
                  + 4 * nb * max(r, 8) * CHUNK)
    return (2 * blocks + state + 12 * r * 4 * CHUNK * CHUNK
            + 24 * 4 * CHUNK * wide + (4 << 20))


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _product(dtype, x, y, dims=_NN):
    """`x @ y` (or as `dims` says) of operands held as `dtype`, summed in
    float32."""
    return lax.dot_general(
        x.astype(dtype), y.astype(dtype), dims,
        precision=_HIGHEST if dtype == _F32 else None,
        preferred_element_type=_F32)


def _pieces(x):
    """A float32 `x` as three bfloat16 pieces whose sum it is (to 2^-24 of
    its size), one under the other along the first axis."""
    high = x.astype(jnp.bfloat16)
    rest = x - high.astype(_F32)
    middle = rest.astype(jnp.bfloat16)
    return jnp.concatenate(
        [high, middle, (rest - middle.astype(_F32)).astype(jnp.bfloat16)])


def _thrice(x):
    """A matrix of zeros and ones as bfloat16, three times one under the
    other: what `_pieces` of the other operand is contracted with, so that
    the matrix unit's float32 sum puts the pieces together again."""
    return jnp.concatenate([x.astype(jnp.bfloat16)] * 3)


def _moved(x, y, dims):
    return lax.dot_general(x, y, dims, preferred_element_type=_F32)


class _Masks:
    """The constant matrices of a chunk of `[Q, Q]` and a group of `r` heads
    of `p` columns, made once a grid step. Each is zeros and ones, and a
    float32 array's three bfloat16 pieces contracted with it are the array's
    own entries, moved or summed, exactly. A TILE is 128 neighbouring
    columns of the group's `r p`: `128 / p` heads side by side, or a part of
    one head."""

    def __init__(self, r: int, p: int):
        q = CHUNK
        self.r, self.p, self.tiles = r, p, r * p // _LANES
        rows, cols = _iota((q, q), 0), _iota((q, q), 1)
        self.lower = cols <= rows
        # [i, k] = 1 where k <= i: sums a row of steps up to each token
        self.summed = self.lower.astype(jnp.bfloat16)
        self.last = _iota((r, q), 1) == q - 1
        # [h, h' Q + j] = 1 where h' = h: head h's row down the rows of a
        # tile of its own
        self.down = _thrice(_iota((r, r * q), 1) // q == _iota((r, r * q), 0))
        # [s R + h, t 256 + s 128 + l] = 1 where lane l of tile t is head
        # h's: the first (s = 0) and the second of two rows a head, each
        # down the rows of the head's columns
        two, cols = _iota((2 * r, 2 * r * p), 0), _iota((2 * r, 2 * r * p), 1)
        lane = cols % _LANES
        head = (cols // (2 * _LANES) * _LANES + lane) // p
        self.heads = _thrice(
            two == cols // _LANES % 2 * r + head)
        # [h, t 128 + l] = 1 where lane l of tile t is head h's
        self.whose = (_iota((r, r * p), 1) // p == _iota((r, r * p), 0))
        lanes = _iota((q, _LANES), 1)
        # a tile's heads, and which of its lanes are whose (None: all)
        self.heads_of = [
            sorted({(t * _LANES + l) // p for l in range(_LANES)})
            for t in range(self.tiles)]
        self.mine = [lanes // p == u for u in range(_LANES // p)]

    def lanes_of(self, t: int, h: int):
        """Which lanes of tile t are head h's (None: all of them)."""
        return None if self.p >= _LANES else self.mine[h % (_LANES // self.p)]

    def running(self, steps):
        """A chunk's `dt a` [R, Q] summed along the chunk, a token a lane."""
        return _apart(_moved(_pieces(steps), self.summed, _NT), self.r, 0)

    def summed_back(self, rows):
        """[R, Q]: each token's sum over the tokens from it on."""
        return _apart(_moved(_pieces(rows), self.summed, _NN), self.r, 0)

    def down_rows(self, pieces, h: int):
        """The pieces of [R, Q] -> [Q, Q]: head h's row as a column, along
        all the lanes."""
        return _moved(pieces, self.down[:, CHUNK * h:CHUNK * (h + 1)], _TN)

    def columns(self, pieces, t: int):
        """The pieces of two of [R, Q], one under the other -> two of [Q,
        128]: a head's row down the rows of its columns in tile t."""
        both = _moved(pieces,
                      self.heads[:, 2 * _LANES * t:2 * _LANES * (t + 1)], _TN)
        return both[:, :_LANES], both[:, _LANES:]

    def over_heads(self, tile, t: int):
        """[Q, 128] of tile t -> [R, Q]: the sum over each head's columns
        (zero for the heads of other tiles), a token a lane."""
        whose = self.whose[:, _LANES * t:_LANES * (t + 1)].astype(
            jnp.bfloat16)
        return _apart(_moved(whose, _pieces(tile), _NT), CHUNK, 1)


def _apart(x, size: int, axis: int):
    """The sum of the three runs of `size` along `axis`: a product's three
    pieces, put together."""
    first, second, third = (lax.slice_in_dim(x, size * i, size * (i + 1),
                                             axis=axis) for i in range(3))
    return first + (second + third)


def _rows(i):
    return pl.ds(pl.multiple_of(i * CHUNK, CHUNK), CHUNK)


def _tile(t: int):
    return slice(_LANES * t, _LANES * (t + 1))


def _only(lanes, x):
    """x with the other lanes zeroed."""
    return x if lanes is None else jnp.where(lanes, x, jnp.zeros_like(x))


class _Chunk:
    """A chunk's factors for one group, computed from its inputs (the
    forward pass and the backward pass compute the same): what all the
    group's heads share, then a head's and a tile's by method."""

    def __init__(self, masks: _Masks, dtype, dt, a, b_in, c_in):
        """dt [R, Q] float32, a [R, 1], b_in and c_in [Q, N]."""
        self.masks, self.dtype, self._heads = masks, dtype, {}
        self.dt, self.a, self.b_in, self.c_in = dt, a, b_in, c_in
        self.run = masks.running(dt * a)                    # G, [R, Q]
        self.run_pieces = _pieces(self.run)
        self.last = self.run[:, CHUNK - 1:]                 # [R, 1]
        self.left = jnp.exp(self.last - self.run)           # [R, Q]
        self.weight = dt * self.left
        # exp(G) and dt exp(G_last - G), to go down the rows of a head's
        # columns
        self.both = _pieces(jnp.concatenate(
            [jnp.exp(self.run), self.weight]))
        self.cb = _product(dtype, c_in, b_in, _NT)          # [Q, Q]

    def head(self, h: int):
        """(exp(G_i - G_j) of head h, zero above the diagonal; its M as the
        products take it), computed where it is first asked for."""
        if h not in self._heads:
            decay = jnp.exp(jnp.where(
                self.masks.lower,
                self.masks.down_rows(self.run_pieces, h)
                - self.run[h:h + 1, :], _MASKED))
            self._heads[h] = decay, (self.cb * decay * self.dt[h:h + 1, :]
                                     ).astype(self.dtype)
        return self._heads[h]

    def columns(self, t: int):
        """exp(G) and dt exp(G_last - G) over tile t: two of [Q, 128]."""
        return self.masks.columns(self.both, t)

    def mixed(self, x, t: int, dims=_NN):
        """`M x` over tile t, [Q, 128] float32: each head's `M_h` times the
        tile's x with the other heads' columns zeroed (`dims`: or its
        transpose times)."""
        return sum(_product(self.dtype, self.head(h)[1],
                            _only(self.masks.lanes_of(t, h), x), dims)
                   for h in self.masks.heads_of[t])


def _chunk_of(masks, dtype, dt_ref, a_ref, b_ref, c_ref, i):
    rows = _rows(i)
    return _Chunk(masks, dtype, dt_ref[i], a_ref[...], b_ref[rows, :],
                  c_ref[rows, :])


def _forward_kernel(nb, r, p, keep, x_ref, dt_ref, a_ref, b_ref, c_ref,
                    y_ref, final_ref, *rest):
    kept_ref, state_ref = rest if keep else (None,) + rest
    dtype = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros(state_ref.shape, _F32)

    masks = _Masks(r, p)

    def chunk(i, _):
        at = _chunk_of(masks, dtype, dt_ref, a_ref, b_ref, c_ref, i)
        rows, b_across = _rows(i), at.b_in.T
        # every head's M first: the matrix unit's products issue in the
        # program's order, and the heads' chains then run side by side
        for h in range(r):
            at.head(h)
        # the group's states through the chunk, a tile at a time
        for t in range(masks.tiles):
            x, state = x_ref[rows, _tile(t)], state_ref[:, _tile(t)]
            if keep:
                kept_ref[i, :, _tile(t)] = state
            gamma, weight = at.columns(t)
            y = at.mixed(x, t) + gamma * _product(
                dtype, at.c_in, state)
            y_ref[rows, _tile(t)] = y.astype(y_ref.dtype)
            written = (x.astype(_F32) * weight).astype(dtype)
            state_ref[:, _tile(t)] = gamma[CHUNK - 1:, :] * state + _product(
                dtype, b_across, written)

    lax.fori_loop(0, nb, chunk, None)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        final_ref[...] = state_ref[...]


def _backward_kernel(nb, r, p, x_ref, dt_ref, a_ref, b_ref, c_ref, kept_ref,
                     dy_ref, dfinal_ref, dx_ref, ddt_ref, da_ref, db_ref,
                     dc_ref, dstate_ref):
    dtype = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[...] = dfinal_ref[...]

    masks = _Masks(r, p)

    def chunk(j, _):
        i = nb - 1 - j
        at = _chunk_of(masks, dtype, dt_ref, a_ref, b_ref, c_ref, i)
        rows = _rows(i)
        product = functools.partial(_product, dtype)
        c_across = at.c_in.T
        # what the heads' sides give their group: the cotangent of C B^T,
        # of B and C through the state, and (a token a lane) of dt where it
        # multiplies, of the running sum and of the last running sum
        d_cb = jnp.zeros((CHUNK, CHUNK), _F32)
        d_b = jnp.zeros(at.b_in.shape, _F32)
        d_c = jnp.zeros(at.c_in.shape, _F32)
        d_dt = jnp.zeros((r, CHUNK), _F32)
        d_run = jnp.zeros((r, CHUNK), _F32)
        d_last = jnp.zeros((r, 1), _F32)
        for t in range(masks.tiles):
            x, dy = x_ref[rows, _tile(t)], dy_ref[rows, _tile(t)]
            state, d_after = kept_ref[i, :, _tile(t)], dstate_ref[:, _tile(t)]
            for h in masks.heads_of[t]:
                at.head(h)
            gamma, weight = at.columns(t)
            kept = gamma[CHUNK - 1:, :]
            reads = product(at.c_in, state)                     # C S
            y = at.mixed(x, t) + gamma * reads
            d_reads = (gamma * dy.astype(_F32)).astype(dtype)
            written = (x.astype(_F32) * weight).astype(dtype)
            d_written = product(at.b_in, d_after)               # [Q, 128]
            d_c = d_c + product(d_reads, state, _NT)
            d_b = d_b + product(written, d_after, _NT)
            dstate_ref[:, _tile(t)] = kept * d_after + product(
                c_across, d_reads)
            d_x = d_written * weight + at.mixed(dy, t, _TN)
            dx_ref[rows, _tile(t)] = d_x.astype(dx_ref.dtype)
            # through exp(G): sum over a head's columns of dY Y
            d_run = d_run + masks.over_heads(dy.astype(_F32) * y, t)
            # through dt exp(G_last - G): of dW x
            d_weight = masks.over_heads(d_written * x.astype(_F32), t)
            d_dt = d_dt + d_weight * at.left
            d_weight = d_weight * at.weight
            d_run = d_run - d_weight
            whose = masks.whose[:, _tile(t)]
            through_kept = jnp.sum(
                jnp.where(whose, jnp.sum(d_after * state, axis=0,
                                         keepdims=True) * kept, 0.0),
                axis=1, keepdims=True)
            d_last = d_last + through_kept + jnp.sum(d_weight, axis=1,
                                                     keepdims=True)
            for h in masks.heads_of[t]:
                decay, within = at.head(h)
                d_m = product(_only(masks.lanes_of(t, h), dy), x, _NT)
                d_within = d_m * decay
                d_cb = d_cb + d_within * at.dt[h:h + 1, :]
                mine = _iota((r, CHUNK), 0) == h
                d_dt = d_dt + jnp.where(
                    mine, jnp.sum(d_within * at.cb, axis=0, keepdims=True),
                    0.0)
                # through exp(-G_j): sum over i of dM M, with the M that
                # `y` was computed from (the rounded one): what `dY Y` gave
                # the rows of this matrix it takes from its columns entry
                # for entry, and the decay's cotangent is what is left of
                # sums that cancel
                d_run = d_run - jnp.where(
                    mine, jnp.sum(d_m * within.astype(_F32), axis=0,
                                  keepdims=True), 0.0)
        d_run = d_run + jnp.where(masks.last, d_last, 0.0)
        d_steps = masks.summed_back(d_run)                  # of dt a
        ddt_ref[i] = d_dt + d_steps * at.a
        da_ref[i] = d_steps * at.dt
        d_cb = d_cb.astype(dtype)
        db_ref[rows, :] = (d_b + product(d_cb, at.c_in, _TN)).astype(
            db_ref.dtype)
        dc_ref[rows, :] = (d_c + product(d_cb, at.b_in)).astype(dc_ref.dtype)

    lax.fori_loop(0, nb, chunk, None)


def _params(need: int):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=need)


def _layout(xbc, dt, a, groups: int, n: int):
    """The arguments as the kernels' blocks cut them (`[x | B | C]` as it
    comes, once for each of the three), and the sizes."""
    (b, t, width), h = xbc.shape, dt.shape[-1]
    g, r = groups, h // groups
    p, c = (width - 2 * g * n) // h, t // CHUNK
    steps = jnp.transpose(dt.astype(_F32).reshape(b, c, CHUNK, g, r),
                          (0, 3, 1, 4, 2))      # [B, G, chunks, R, Q]
    return ((xbc, steps, a.astype(_F32).reshape(g, r, 1), xbc, xbc),
            (b, t, g, r, p, n, c, chunks_a_step(c)))


def _specs(sizes, reverse: bool):
    """The block specifications of a group's x (in `[x | B | C]` and alone),
    of dt, of a, of B or C by their first block in `[x | B | C]` (or 0:
    alone), of the kept states and of a group's states (final, or their
    cotangent)."""
    _, _, g, r, p, n, c, nb = sizes
    blocks = c // nb

    def at(block):
        return blocks - 1 - block if reverse else block

    def narrow(first: int):
        return pl.BlockSpec((None, nb * CHUNK, n),
                            lambda b, g, block: (b, at(block), first + g))
    return (
        pl.BlockSpec((None, nb * CHUNK, r * p),
                     lambda b, g, block: (b, at(block), g)),
        pl.BlockSpec((None, None, nb, r, CHUNK),
                     lambda b, g, block: (b, g, at(block), 0, 0)),
        pl.BlockSpec((None, r, 1), lambda b, g, block: (g, 0, 0)),
        narrow,
        pl.BlockSpec((None, None, nb, n, r * p),
                     lambda b, g, block: (b, g, at(block), 0, 0)),
        pl.BlockSpec((None, None, n, r * p),
                     lambda b, g, block: (b, g, 0, 0)))


def _in_xbc(sizes, narrow):
    """B's and C's specifications in `[x | B | C]`."""
    _, _, g, r, p, n, _, _ = sizes
    first = g * r * p // n
    return narrow(first), narrow(first + g)


def _cost(sizes, itemsize: int, passes: int):
    """The chunked form's products and bytes, for XLA's scheduler."""
    b, t, g, r, p, n, _, _ = sizes
    chunk = 2 * CHUNK * (CHUNK * n + r * p * (CHUNK + 2 * n))
    return pl.CostEstimate(
        flops=passes * b * g * (t // CHUNK) * chunk,
        transcendentals=passes * b * g * r * t * (CHUNK + 2),
        bytes_accessed=passes * b * t * (
            itemsize * 2 * g * (r * p + n) + 4 * g * r))


def _forward(xbc, dt, a, groups: int, n: int, keep: bool, interpret: bool):
    args, sizes = _layout(xbc, dt, a, groups, n)
    b, t, g, r, p, n, c, nb = sizes
    wide, steps, decays, narrow, kept, states = _specs(sizes, False)
    out_shape = [jax.ShapeDtypeStruct((b, t, g * r * p), xbc.dtype),
                 jax.ShapeDtypeStruct((b, g, n, r * p), _F32)]
    out_specs = [wide, states]
    if keep:
        out_shape.append(jax.ShapeDtypeStruct((b, g, c, n, r * p), _F32))
        out_specs.append(kept)
    out = pl.pallas_call(
        functools.partial(_forward_kernel, nb, r, p, keep),
        name="ssd_fwd_kept" if keep else "ssd_fwd", interpret=interpret,
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(b, g, c // nb),
            in_specs=[wide, steps, decays, *_in_xbc(sizes, narrow)],
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((n, r * p), _F32)]),
        compiler_params=_params(vmem_bytes(
            nb, r, p, n, xbc.dtype.itemsize, False)),
        cost_estimate=_cost(sizes, xbc.dtype.itemsize, 1))(*args)
    # the states' heads apart: [B, G, N, R P] -> [B, H, N, P]
    final = jnp.transpose(out[1].reshape(b, g, n, r, p),
                          (0, 1, 3, 2, 4)).reshape(b, g * r, n, p)
    return (out[0], final) + tuple(out[2:])


def _backward(xbc, dt, a, kept, dy, dfinal, groups: int, n: int,
              interpret: bool):
    args, sizes = _layout(xbc, dt, a, groups, n)
    b, t, g, r, p, n, c, nb = sizes
    wide, steps, decays, narrow, kept_spec, states = _specs(sizes, True)
    # the final state's cotangent as the kernel holds the states:
    # [B, H, N, P] -> [B, G, N, R P]
    dfinal = jnp.transpose(dfinal.astype(_F32).reshape(b, g, r, n, p),
                           (0, 1, 3, 2, 4)).reshape(b, g, n, r * p)
    by_chunk = jax.ShapeDtypeStruct(args[1].shape, _F32)
    by_group = jax.ShapeDtypeStruct((b, t, g * n), xbc.dtype)
    dx, ddt, da, db, dc = pl.pallas_call(
        functools.partial(_backward_kernel, nb, r, p),
        name="ssd_bwd", interpret=interpret,
        out_shape=[jax.ShapeDtypeStruct(dy.shape, xbc.dtype), by_chunk,
                   by_chunk, by_group, by_group],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(b, g, c // nb),
            in_specs=[wide, steps, decays, *_in_xbc(sizes, narrow),
                      kept_spec, wide, states],
            out_specs=[wide, steps, steps, narrow(0), narrow(0)],
            scratch_shapes=[pltpu.VMEM((n, r * p), _F32)]),
        compiler_params=_params(vmem_bytes(
            nb, r, p, n, xbc.dtype.itemsize, True)),
        cost_estimate=_cost(sizes, xbc.dtype.itemsize, 3))(
            *args, kept, dy, dfinal)
    # [B, G, chunks, R, Q] -> [B, T, H]
    ddt = jnp.transpose(ddt, (0, 2, 4, 1, 3)).reshape(dt.shape)
    return (jnp.concatenate([dx, db, dc], axis=-1), ddt.astype(dt.dtype),
            jnp.sum(da, axis=(0, 2, 4)).reshape(a.shape).astype(a.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def ssd_scan(xbc, dt, a, groups: int, n: int, interpret: bool = False):
    """Mamba-2's scan over a sequence in chunks of 128 (the module's head
    has the shapes). Returns (y [B, T, H x P] as `xbc.dtype`, without the `D
    x` term; the state after the last token [B, H, N, P] float32).
    `interpret`: under Pallas' interpreter (the CPU tests)."""
    return _forward(xbc, dt, a, groups, n, False, interpret)


def _scan_fwd(xbc, dt, a, groups, n, interpret):
    y, final, kept = _forward(xbc, dt, a, groups, n, True, interpret)
    return (y, final), (xbc, dt, a, kept)


def _scan_bwd(groups, n, interpret, res, cotangents):
    dy, dfinal = cotangents
    return _backward(*res, dy, dfinal, groups, n, interpret)


ssd_scan.defvjp(_scan_fwd, _scan_bwd)
