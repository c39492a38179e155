"""The chunked gated delta rule as Pallas TPU kernels (PR 45): what
`models/blocks/delta.chunked_rule` states, forward and backward, with the
state in VMEM from a sequence's first chunk to its last.

    gated_delta_rule(q, k, v, g, beta, dk)  ->  (o, the last token's state)

`q`, `k` [B, T, Hk x dk] and `v` [B, T, H x dv] in the products' dtype, heads
side by side along the last axis as the kernels' blocks cut them and as
`ops/delta_prologue.py`'s kernels write them (since PR 47: no four-dimensional
array, no relayout between the two; each key head serves `H / Hk` value
heads, neighbours together), `g` and `beta` [B, T, H] float32, `dk` a key
head's width; o comes back `[B, T, H x dv]`. The algebra of a chunk is
`blocks/delta.py`'s head; here ALL of it happens in VMEM: the running sum of
`g` and the decay mask, `K K^T` and `Q K^T`, the float32 inverse of `I + A`,
`W`, `U`, `V' = U - W S`, `O` and the state's update. The products take their
operands in `q.dtype` and sum in float32 (float32 operands: at `highest`);
the state, the decays and the inverse are float32; only differences that are
<= 0 are exponentiated.

**The grid** runs over (batch, key head, blocks of chunks), the last
sequential: a step reads `(chunks x 64, 128)` of q and k and `(chunks x 64,
heads x 128)` of v where the mixer has them (`[B, T, heads x 128]`, the key
head's lane offset by the index map: q and k are read ONCE for the value heads
they serve, neighbours in v, and `Q K^T` and `K K^T` are computed once for
them; no `repeat`), loops over its chunks with the state `[dk, dv]` float32 of
each of the key head's value heads in a VMEM scratch, and writes o once. `g`
and `beta` arrive as `[B, H, blocks, chunks, 64]` (4 MB transposed by XLA: a
token's gate has to lie along the lanes for a block to hold it), a chunk a
row; the kernel turns a row into a column where it needs one by a masked sum.
How many chunks a step takes comes from the shape (`chunks_a_step`),
`vmem_limit_bytes` from the blocks.

**Three calls, a name each** (a device trace shows them):

  gdn_fwd       o and the final state; keeps nothing (what runs where
                nothing is differentiated)
  gdn_fwd_kept  the same and every chunk's incoming state, `[B, H, chunks,
                dk, dv]` float32: what the backward pass needs of the
                recurrence (537 MB a layer at 2 x 8192 tokens and 32 heads,
                as `chunked_rule` keeps them; written beside the products,
                which bound the kernel). Both forward passes of a layer
                under `jax.checkpoint` are this call: JAX evaluates a
                `custom_vjp`'s forward RULE in the first pass too, and
                `defvjp(optimize_remat=True)`, which would run `gdn_fwd`
                there, hands the kernel an `op_name` without its path
                (`gdn_fwd/pallas_call`), which a trace's readers could no
                longer book under the mixer's scope
  gdn_bwd       ONE kernel, the chunks in reverse with the state's cotangent
                in the scratch: a chunk's factors are computed again from its
                five inputs and its kept state, and all five cotangents leave
                it (dq and dk summed over a key head's value heads before
                they are written).
                flash-linear-attention splits this in a reverse sweep for
                the state's cotangent and a kernel parallel over chunks; with
                the chunk axis sequential anyway (one core a chip) the split
                would only write the cotangents of 8192 states out and read
                them again.

**A round of eight.** A chunk is one long chain of dependent operations, and
the compiler overlaps independent ones only inside one body of a loop: so a
body of the kernels' loop takes eight chunks (`_ROUND`: four of each of two
value heads), reads all their inputs first, computes their chains side by
side, runs the heads' recurrences through them side by side (a recurrence is
two dependent products a chunk, 490 cycles that only another head's can
fill), and stores last, with no branch in between. One chunk a body left half
the instruction slots empty: 3 052 scheduled bundles a chunk forward, 981 in
such rounds (the compiler's own schedule, device-less; PERF.md section 6,
PR 45).

**The inverse** of `I + A` (unit lower triangular, 64 x 64) is computed in the
kernel in float32 and no power of `A` is formed. The 16 x 16 diagonal blocks
by forward substitution on the vector unit, sixteen blocks (four chunks') in
one `[64, 64]` array at a time (`_substituted`: whole vregs change places to
pack them, the step's column of multipliers is one permutation of lanes a
vreg); then joined 16 -> 32 -> 64 by `[[M11, 0], [-M22 A21 M11, M22]]`, two
chunks side by side, the products at full float32 precision (`_exact`: the
six bfloat16 products that `highest` is, the pieces cut here so that a piece
of the right operand is latched into the matrix unit once).
"""

from __future__ import annotations

import functools
from typing import NamedTuple
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64              # tokens to a chunk (`blocks/delta.CHUNK` is this)
_SUBSTITUTED = 16       # the diagonal blocks that are inverted row by row
_LANES = 128
_MOST_CHUNKS = 16       # a grid step's chunks at most
_ROUND = 8              # chunks to a round of the kernels' loops, at most
_HIGHEST = lax.Precision.HIGHEST
_F32 = jnp.float32
# dimension numbers of `lax.dot_general` for x @ y, x @ y.T and x.T @ y
_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def chunks_a_step(chunks: int) -> int:
    """How many chunks one grid step goes through: the largest divisor of
    the sequence's `chunks` up to 16. A step's work then stands well over
    the grid's own ~0.35 us (16 chunks of one head are ~200 MFLOP)."""
    return next(n for n in range(min(chunks, _MOST_CHUNKS), 0, -1)
                if chunks % n == 0)


def takes(q_shape, v_shape) -> bool:
    """Whether the kernels take the shape: key and value heads of whole
    128-lane blocks, a sequence of whole chunks, value heads a multiple of
    the key heads."""
    (_, t, hk, dk), (_, _, h, dv) = q_shape, v_shape
    return (t > 0 and t % CHUNK == 0 and h % hk == 0
            and dk % _LANES == 0 and dv % _LANES == 0)


def vmem_bytes(nb: int, rep: int, dk: int, dv: int, itemsize: int,
               backward: bool) -> int:
    """VMEM of a call's blocks (each twice: the pipeline's two buffers),
    its scratch and what a round's chunks spill from the registers (a
    generous three dozen `[64, dk]` float32 a chunk and head)."""
    rows = nb * CHUNK
    state = 4 * dk * dv
    given = itemsize * rows * (2 * dk + rep * dv) + rep * 2 * 4 * rows
    blocks = given + itemsize * rows * rep * dv + rep * (nb + 1) * state
    if backward:
        blocks = 2 * given + itemsize * rows * rep * dv \
            + rep * (nb + 2) * state
    return (2 * blocks + rep * state
            + _ROUND * 36 * 4 * CHUNK * max(dk, dv) + (4 << 20))


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
    its size)."""
    high = x.astype(jnp.bfloat16)
    rest = x - high.astype(_F32)
    middle = rest.astype(jnp.bfloat16)
    return high, middle, (rest - middle.astype(_F32)).astype(jnp.bfloat16)


def _exact(x, y):
    """`x @ y` at full float32 precision: the six bfloat16 products that
    `highest` is on this chip, but each piece of `y` latched into the
    matrix unit ONCE for all the pieces of `x` it meets (Mosaic's own
    `highest` latches a 128 x 128 float32 tile for each of the six: 96
    latches for a product of 32 x 64 x 64, where this is 24)."""
    r = x.shape[0]
    (x1, x2, x3), (y1, y2, y3) = _pieces(x), _pieces(y)

    def product(pieces, y):
        return lax.dot_general(jnp.concatenate(pieces), y, _NN,
                               preferred_element_type=_F32)
    a, b, c = product((x1, x2, x3), y1), product((x1, x2), y2), product(
        (x1,), y3)
    return a[:r] + ((a[r:2 * r] + b[:r]) + (a[2 * r:] + b[r:] + c))


class _Masks:
    """The masks of a `[C, C]` chunk, made once a grid step (the kernels'
    loop over chunks would make them again every round)."""

    def __init__(self, c: int):
        rows, cols = _iota((c, c), 0), _iota((c, c), 1)
        self.c = c
        self.eye, self.lower, self.strict = (rows == cols, cols <= rows,
                                             cols < rows)
        s = self.s = min(_SUBSTITUTED, c)   # the side of a substituted block
        # the block of side s under the diagonal in every block of side 2 s
        self.below = {}
        while s < c:
            self.below[s] = ((rows & s) != 0) & (
                (rows & -s) - s == (cols & -s))
            s *= 2

    def column(self, row):
        """A row `[1, C]` as a column `[C, 1]`."""
        return jnp.sum(jnp.where(self.eye, row, 0.0), axis=1, keepdims=True)

    def row(self, column):
        """A column `[C, 1]` as a row `[1, C]`."""
        return jnp.sum(jnp.where(self.eye, column, 0.0), axis=0,
                       keepdims=True)


def _substituted(masks: _Masks, many):
    """`(I + a)^-1 - I` on the diagonal blocks of side `s` of every strictly
    lower `a` [C, C] of `many`, by forward substitution: row j of a block
    done, its share `a[i, j] * row j` goes to the rows i below it. The
    blocks of `C / s` chunks at once, in one `[C, C]` array: block b of the
    group's chunk i stays in its lanes and goes to the rows of block `(b +
    i) % blocks` (whole vregs change places, no lane moves: a rotation of
    lanes costs 40 cycles, a permutation 9, and a single chunk's blocks
    fill a quarter of the array). A step's column of multipliers is one
    permutation of lanes a vreg."""
    c, s = masks.c, masks.s
    blocks = c // s
    if len(many) > blocks:
        return (_substituted(masks, many[:blocks])
                + _substituted(masks, many[blocks:]))
    of_block = _iota((s, c), 1) & -s

    def rows(x, b):
        return x[s * b:s * (b + 1)]

    def placed(get):
        """[C, C] whose rows of block r hold, in the lanes of block b,
        `get(i, b)` with `i = (r - b) % blocks` (a chunk of the group or
        nothing)."""
        return jnp.concatenate([
            sum(jnp.where(of_block == s * b, get((r - b) % blocks, b), 0.0)
                for b in range(blocks) if (r - b) % blocks < len(many))
            for r in range(blocks)])

    n = -placed(lambda i, b: rows(many[i], b))
    wide = jnp.zeros((c, _LANES - c), _F32)
    n, t = (jnp.concatenate([x, wide], axis=1) for x in (n, n))
    lanes = _iota(n.shape, 1)
    for j in range(s - 1):
        # `n[:, j]` of every block, along its 16 lanes; zero from row j up
        column = jnp.take_along_axis(n, (lanes & -s) + j, axis=1)
        row = jnp.broadcast_to(t.reshape(blocks, s, _LANES)[:, j:j + 1],
                               (blocks, s, _LANES)).reshape(c, _LANES)
        t = t + column * row
    t = t[:, :c]
    return [jnp.concatenate([
        jnp.where(of_block == s * b, rows(t, (b + i) % blocks), 0.0)
        for b in range(blocks)]) for i in range(len(many))]


def _side_by_side(pair):
    """[[x0, 0], [0, x1]] of two matrices of one shape (or the one)."""
    if len(pair) == 1:
        return pair[0]
    zeros = jnp.zeros_like(pair[0])
    return jnp.concatenate([jnp.concatenate([pair[0], zeros], axis=1),
                            jnp.concatenate([zeros, pair[1]], axis=1)])


def _unit_lower_inverses(masks: _Masks, many):
    """`(I + a)^-1` of every strictly lower `a` [C, C] float32 of `many`
    (the module's head says how). The joins take the chunks two at a time,
    side by side along the lanes against their matrices on one diagonal: a
    product of 32 x 128 x 128 costs the matrix unit what one of 32 x 64 x
    64 does."""
    c = masks.c
    blocks = [jnp.where(masks.eye, 1.0, m)
              for m in _substituted(masks, many)]
    out = []
    for first in range(0, len(many), 2):
        pair, ms = many[first:first + 2], blocks[first:first + 2]
        s = masks.s
        while s < c:
            # [[m11, 0], [-m22 a21 m11, m22]] in every block of side 2 s:
            # only its lower rows change, so only they are multiplied
            below = [jnp.where(masks.below[s], a, 0.0) for a in pair]
            lower = [jnp.concatenate([m[i:i + s]
                                      for i in range(s, c, 2 * s)])
                     for m in ms]
            both = _exact(_exact(jnp.concatenate(lower, axis=1),
                                 _side_by_side(below)), _side_by_side(ms))
            lower = [x - both[:, c * i:c * (i + 1)]
                     for i, x in enumerate(lower)]
            ms = [jnp.concatenate(
                [x[(i - s) // 2:(i + s) // 2] if i // s % 2 else m[i:i + s]
                 for i in range(0, c, s)]) for m, x in zip(ms, lower)]
            s *= 2
        out += ms
    return out


class _Keys:
    """What a chunk's q and k give, the same for every value head of their
    key head: `Q K^T` and `K K^T`, float32 sums of `dtype` operands."""

    def __init__(self, q, k):
        self.dtype = q.dtype
        self.q, self.k = q.astype(_F32), k.astype(_F32)
        self.qk = _product(q.dtype, q, k, _NT)
        self.kk = _product(q.dtype, k, k, _NT)


class _Back(NamedTuple):
    """What `_Chunk.backward` gives: the cotangent of the state before the
    chunk; this head's share of dq and of dk and of the cotangents of `Q
    K^T` and `K K^T` (its key head's products take those summed over the
    heads); dv; dg and dbeta as rows."""
    d_state: jax.Array
    dq: jax.Array
    dk: jax.Array
    d_qk: jax.Array
    d_kk: jax.Array
    dv: jax.Array
    dg: jax.Array
    dbeta: jax.Array


class _Chunk:
    """A chunk's factors for one value head, computed from its five inputs
    (the forward pass and the backward pass compute the same)."""

    def __init__(self, masks: _Masks, keys: _Keys, v, g_row, beta_row):
        c = masks.c
        self.masks, self.keys, self.dtype = masks, keys, keys.dtype
        # the running sum of g down the chunk, as a column and as a row
        run = jnp.sum(jnp.where(masks.lower, g_row, 0.0), axis=1,
                      keepdims=True)
        last = run[c - 1:c, :]
        # zero above the diagonal; only differences <= 0 are exponentiated
        self.decay = jnp.where(
            masks.lower, jnp.exp(jnp.minimum(run - masks.row(run), 0.0)), 0.0)
        self.gamma = jnp.exp(run)                   # [C, 1]
        self.left = jnp.exp(last - run)             # [C, 1]
        self.kept = jnp.exp(last)                   # [1, 1]
        self.beta = masks.column(beta_row)
        self.v = v.astype(_F32)
        # beta_i (k_i . k_j) decay_ij under the diagonal
        self.a = jnp.where(masks.strict,
                           self.beta * keys.kk * self.decay, 0.0)
        self.k_beta_gamma = keys.k * (self.beta * self.gamma)
        self.v_beta = self.v * self.beta
        self.within = keys.qk * self.decay
        self.q_gamma = keys.q * self.gamma
        self.k_left = keys.k * self.left

    @staticmethod
    def solve(masks: _Masks, chunks):
        """The inverse, W and U of every chunk of a round."""
        inverses = _unit_lower_inverses(masks, [at.a for at in chunks])
        for at, inverse in zip(chunks, inverses):
            at.inverse = inverse
            at.w = _product(at.dtype, inverse, at.k_beta_gamma)
            at.u = _product(at.dtype, inverse, at.v_beta)
        return chunks

    def writes(self, state):
        """What the chunk really writes: `V' = U - W S`."""
        return self.u - _product(self.dtype, self.w, state)

    def answers(self, state, written):
        """Its output: `(Q exp(G)) S + lower(Q K^T * decay) V'`."""
        return (_product(self.dtype, self.q_gamma, state)
                + _product(self.dtype, self.within, written))

    def after(self, state, written):
        """The state after it."""
        return self.kept * state + _product(self.dtype, self.k_left, written,
                                            _TN)

    def backward(self, state, d_after, do) -> _Back:
        """The chunk's cotangents from the state it came upon, the cotangent
        of the state after it and its output's."""
        masks, keys = self.masks, self.keys
        product = functools.partial(_product, self.dtype)
        written = self.writes(state)
        d_written = (product(self.within, do, _TN)
                     + product(self.k_left, d_after))
        d_before = (self.kept * d_after + product(self.q_gamma, do, _TN)
                    - product(self.w, d_written, _TN))
        d_within = jnp.where(masks.lower, product(do, written, _NT), 0.0)
        d_q_gamma = product(do, state, _NT)
        d_k_left = product(written, d_after, _NT)
        d_w = -product(d_written, state, _NT)
        # through W = M (k beta gamma) and U = M (v beta): the inverse's
        # cotangent -M^T (dW (k beta gamma)^T + dU (v beta)^T) M^T is
        # -(M^T dW) W^T - (M^T dU) U^T
        d_kbg = product(self.inverse, d_w, _TN)
        d_v_beta = product(self.inverse, d_written, _TN)
        d_a = -jnp.where(masks.strict, product(d_kbg, self.w, _NT)
                         + product(d_v_beta, self.u, _NT), 0.0)
        d_x = d_a * self.decay              # of beta_i (k_i . k_j)
        by_k = jnp.sum(d_kbg * keys.k, axis=1, keepdims=True)
        dq = self.gamma * d_q_gamma
        dk = self.left * d_k_left + (self.beta * self.gamma) * d_kbg
        d_beta = (self.gamma * by_k
                  + jnp.sum(d_x * keys.kk, axis=1, keepdims=True)
                  + jnp.sum(d_v_beta * self.v, axis=1, keepdims=True))
        # the running sum's cotangent, then g's: the sum over the rows from
        # a token on
        d_gamma = self.beta * by_k + jnp.sum(d_q_gamma * keys.q, axis=1,
                                             keepdims=True)
        d_left = self.left * jnp.sum(d_k_left * keys.k, axis=1,
                                     keepdims=True)
        both = d_a * self.a + d_within * self.within
        d_run = (self.gamma * d_gamma - d_left
                 + jnp.sum(both, axis=1, keepdims=True)
                 - masks.column(jnp.sum(both, axis=0, keepdims=True)))
        d_last = (jnp.sum(d_left, axis=0, keepdims=True)
                  + self.kept * jnp.sum(jnp.sum(d_after * state, axis=1,
                                                keepdims=True),
                                        axis=0, keepdims=True))
        d_run = d_run + jnp.where(_iota((masks.c, 1), 0) == masks.c - 1,
                                  d_last, 0.0)
        d_g = jnp.sum(jnp.where(masks.lower, d_run, 0.0), axis=0,
                      keepdims=True)
        return _Back(d_before, dq, dk, d_within * self.decay,
                     self.beta * d_x, self.beta * d_v_beta, d_g,
                     masks.row(d_beta))


def _rows(i):
    return pl.ds(pl.multiple_of(i * CHUNK, CHUNK), CHUNK)


def _rounds(nb: int, heads: int, round_):
    """`round_(the chunks of a round)` for the `nb` chunks of a grid step,
    `_ROUND` chunks of one head at a time. A chunk is one long chain of
    dependent operations (fifteen substitution steps, four exact products,
    the state's two), and the compiler overlaps what is independent only
    inside one body of the loop: a round reads all its chunks' inputs
    first, computes their chains side by side and stores last, with no
    branch in between."""
    most = max(1, _ROUND // heads)
    size = next(n for n in range(min(nb, most), 0, -1) if nb % n == 0)
    lax.fori_loop(
        0, nb // size,
        lambda j, _: round_([j * size + u for u in range(size)]), None)


def _solved(masks, refs, chunks):
    """`[chunk][head]`: the factors of every chunk of a round for every
    value head of the key head, solved."""
    q_ref, k_ref, v_ref, g_ref, beta_ref = refs
    heads, dv = g_ref.shape[0], v_ref.shape[1] // g_ref.shape[0]
    ats = []
    for i in chunks:
        rows = _rows(i)
        keys = _Keys(q_ref[rows, :], k_ref[rows, :])
        ats.append([_Chunk(masks, keys, v_ref[rows, dv * h:dv * (h + 1)],
                           g_ref[h, pl.ds(i, 1), :],
                           beta_ref[h, pl.ds(i, 1), :])
                    for h in range(heads)])
    _Chunk.solve(masks, [at for of_chunk in ats for at in of_chunk])
    return ats


def _forward_kernel(nb, keep, q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref,
                    final_ref, *rest):
    kept_ref, state_ref = rest if keep else (None,) + rest
    heads, dv = state_ref.shape[0], state_ref.shape[2]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros(state_ref.shape, _F32)

    masks = _Masks(CHUNK)
    refs = (q_ref, k_ref, v_ref, g_ref, beta_ref)

    def round_(chunks):
        ats = _solved(masks, refs, chunks)
        # the heads' recurrences side by side: each is a chain of two
        # dependent products a chunk that nothing else can shorten
        states, done = [state_ref[h] for h in range(heads)], []
        for of_chunk in ats:
            written = [at.writes(s) for at, s in zip(of_chunk, states)]
            done.append((states, [at.answers(s, w) for at, s, w in zip(
                of_chunk, states, written)]))
            states = [at.after(s, w)
                      for at, s, w in zip(of_chunk, states, written)]
        for i, (before, outs) in zip(chunks, done):
            for h in range(heads):
                o_ref[_rows(i), dv * h:dv * (h + 1)] = outs[h].astype(
                    o_ref.dtype)
                if keep:
                    kept_ref[h, i] = before[h]
        for h in range(heads):
            state_ref[h] = states[h]

    _rounds(nb, heads, round_)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        final_ref[...] = state_ref[...]


def _backward_kernel(nb, q_ref, k_ref, v_ref, g_ref, beta_ref, kept_ref,
                     do_ref, dfinal_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                     dbeta_ref, dstate_ref):
    heads, dv = dstate_ref.shape[0], dstate_ref.shape[2]

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[...] = dfinal_ref[...]

    masks = _Masks(CHUNK)
    refs = (q_ref, k_ref, v_ref, g_ref, beta_ref)

    def round_(chunks):
        chunks = [nb - 1 - i for i in chunks]
        ats = _solved(masks, refs, chunks)
        given = [[(kept_ref[h, i], do_ref[_rows(i), dv * h:dv * (h + 1)])
                  for h in range(heads)] for i in chunks]
        d_states, outs = [dstate_ref[h] for h in range(heads)], []
        for of_chunk, gave in zip(ats, given):
            back = [at.backward(state, d, do)
                    for at, (state, do), d in zip(of_chunk, gave, d_states)]
            d_states = [b.d_state for b in back]
            outs.append(back)
        for i, of_chunk, back in zip(chunks, ats, outs):
            rows, keys = _rows(i), of_chunk[0].keys
            product = functools.partial(_product, keys.dtype)
            # the key head's products, their cotangents summed over its
            # value heads: Q K^T and the symmetric K K^T
            d_qk, d_kk = (sum(b.d_qk for b in back),
                          sum(b.d_kk for b in back))
            dq = sum(b.dq for b in back) + product(d_qk, keys.k)
            dk = (sum(b.dk for b in back) + product(d_qk, keys.q, _TN)
                  + product(d_kk, keys.k) + product(d_kk, keys.k, _TN))
            dq_ref[rows, :] = dq.astype(dq_ref.dtype)
            dk_ref[rows, :] = dk.astype(dk_ref.dtype)
            for h, b in enumerate(back):
                dv_ref[rows, dv * h:dv * (h + 1)] = b.dv.astype(dv_ref.dtype)
                dg_ref[h, pl.ds(i, 1), :] = b.dg
                dbeta_ref[h, pl.ds(i, 1), :] = b.dbeta
        for h in range(heads):
            dstate_ref[h] = d_states[h]

    _rounds(nb, heads, round_)


def _params(need: int):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=need)


def _layout(q, k, v, g, beta, dk: int):
    """The arguments as the kernels' blocks cut them (q, k and v as they
    come: `[B, T, heads x width]`), and the sizes."""
    (b, t, keys), h = q.shape, g.shape[-1]
    hk, dv = keys // dk, v.shape[-1] // h
    n = t // CHUNK
    nb = chunks_a_step(n)

    def gates(a):       # [B, T, H] -> [B, H, blocks, chunks, 64]
        return jnp.moveaxis(a.astype(_F32), 1, 2).reshape(
            b, h, n // nb, nb, CHUNK)
    return ((q, k, v, gates(g), gates(beta)),
            (b, t, hk, h, dk, dv, n, nb))


def _specs(sizes, reverse: bool):
    """The block specifications of q or k, of a key head's v, gates, kept
    states and states (final, or its cotangent)."""
    _, _, hk, h, dk, dv, n, nb = sizes
    rep, blocks = h // hk, n // nb

    def at(block):
        return blocks - 1 - block if reverse else block
    return (
        pl.BlockSpec((None, nb * CHUNK, dk),
                     lambda b, key, block: (b, at(block), key)),
        pl.BlockSpec((None, nb * CHUNK, rep * dv),
                     lambda b, key, block: (b, at(block), key)),
        pl.BlockSpec((None, rep, None, nb, CHUNK),
                     lambda b, key, block: (b, key, at(block), 0, 0)),
        pl.BlockSpec((None, rep, nb, dk, dv),
                     lambda b, key, block: (b, key, at(block), 0, 0)),
        pl.BlockSpec((None, None, rep, dk, dv),
                     lambda b, key, block: (b, key, 0, 0, 0)))


def _forward(q, k, v, g, beta, dk: int, keep: bool, interpret: bool):
    args, sizes = _layout(q, k, v, g, beta, dk)
    b, t, hk, h, dk, dv, n, nb = sizes
    rep = h // hk
    keys, values, gates, kept, states = _specs(sizes, False)
    out_shape = [jax.ShapeDtypeStruct((b, t, h * dv), v.dtype),
                 jax.ShapeDtypeStruct((b, hk, rep, dk, dv), _F32)]
    out_specs = [values, states]
    if keep:
        out_shape.append(jax.ShapeDtypeStruct((b, h, n, dk, dv), _F32))
        out_specs.append(kept)
    out = pl.pallas_call(
        functools.partial(_forward_kernel, nb, keep),
        name="gdn_fwd_kept" if keep else "gdn_fwd", interpret=interpret,
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(b, hk, n // nb),
            in_specs=[keys, keys, values, gates, gates],
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((rep, dk, dv), _F32)]),
        compiler_params=_params(vmem_bytes(
            nb, rep, dk, dv, q.dtype.itemsize, False)),
        cost_estimate=_cost(sizes, q.dtype.itemsize, 1))(*args)
    return (out[0], out[1].reshape(b, h, dk, dv)) + tuple(out[2:])


def _cost(sizes, itemsize: int, passes: int):
    """The chunked form's products and bytes, for XLA's scheduler."""
    b, t, hk, h, dk, dv, _, _ = sizes
    chunk = 2 * CHUNK * (2 * CHUNK * dk + CHUNK * (dk + dv)
                         + 3 * dk * dv + CHUNK * dv)
    return pl.CostEstimate(
        flops=passes * b * h * (t // CHUNK) * chunk,
        transcendentals=passes * b * h * t * (CHUNK + 3),
        bytes_accessed=passes * b * t * (
            itemsize * (2 * hk * dk + 2 * h * dv) + 8 * h))


def _backward(q, k, v, g, beta, kept, do, dfinal, dk: int, interpret: bool):
    args, sizes = _layout(q, k, v, g, beta, dk)
    b, t, hk, h, dk, dv, n, nb = sizes
    rep = h // hk
    keys, values, gates, kept_spec, states = _specs(sizes, True)
    dq, dk_, dv_, dg, dbeta = pl.pallas_call(
        functools.partial(_backward_kernel, nb),
        name="gdn_bwd", interpret=interpret,
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(b, hk, n // nb),
            in_specs=[keys, keys, values, gates, gates, kept_spec, values,
                      states],
            out_specs=[keys, keys, values, gates, gates],
            scratch_shapes=[pltpu.VMEM((rep, dk, dv), _F32)]),
        compiler_params=_params(vmem_bytes(
            nb, rep, dk, dv, q.dtype.itemsize, True)),
        cost_estimate=_cost(sizes, q.dtype.itemsize, 3))(
            *args, kept, do,
            dfinal.astype(_F32).reshape(b, hk, rep, dk, dv))

    def gates_back(a):  # [B, H, blocks, chunks, 64] -> [B, T, H]
        return jnp.moveaxis(a.reshape(b, h, t), 1, 2)
    return (dq, dk_, dv_, gates_back(dg).astype(g.dtype),
            gates_back(dbeta).astype(beta.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def gated_delta_rule(q, k, v, g, beta, dk: int, interpret: bool = False):
    """The gated delta rule over a sequence in chunks of 64 (the module's
    head has the shapes). Returns (o [B, T, H x dv] as `v.dtype`, the state
    after the last token [B, H, dk, dv] float32). `interpret`: under
    Pallas' interpreter (the CPU tests)."""
    return _forward(q, k, v, g, beta, dk, False, interpret)


def _rule_fwd(q, k, v, g, beta, dk, interpret):
    o, final, kept = _forward(q, k, v, g, beta, dk, True, interpret)
    return (o, final), (q, k, v, g, beta, kept)


def _rule_bwd(dk, interpret, res, cotangents):
    do, dfinal = cotangents
    return _backward(*res, do, dfinal, dk, interpret)


gated_delta_rule.defvjp(_rule_fwd, _rule_bwd)
