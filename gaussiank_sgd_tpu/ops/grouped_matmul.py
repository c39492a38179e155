"""Grouped matrix products as Pallas kernels whose tiles are computed from
the operands' shapes: the experts' three products of `models/blocks/experts.py`
on the TPU (PR 41), where XLA's own kernel for `lax.ragged_dot` takes no
tile sizes from its caller and cuts an expert of 2304 x 896 badly.

    grouped(x, w, sizes)                  x[rows of group e] @ w[e]
    grouped(g, w, sizes, transposed=True) g[rows of group e] @ w[e].T
    grouped_by_group(x, g, sizes)         x[rows of e].T @ g[rows of e], f32

The rows of group e are the `sizes[e]` rows after those of the groups
before it. The schedule is that of megablox (`jax.experimental.pallas.ops.
tpu.megablox`): a row tile is visited once for every group that has rows in
it and no tile past the last group's end is visited at all, so the rows
past it are NOT written (the caller zeroes them: `experts._live_rows`).
What differs from megablox's `gmm` and `tgmm`: the schedule is a dozen
operations on arrays of `row tiles + groups` entries (`_schedule`); the
tiles come from `tiles` / `tiles_by_group` (whole contraction and whole
width wherever they fit, so a group's matrix is read from HBM once and
stays for all of the group's row tiles) and `vmem_limit_bytes` from the
tiles (megablox sets none, and the compiler's default refuses these);
a product whose contraction is one tile has no accumulator; the product by
group masks only the tiles a group's end cuts (one in nine at 256 rows a
tile and 2048 a group; megablox converts every tile of both operands to
float32, masks it and transposes it), contracts the rows as they lie and
accumulates into its float32 output block; and each call carries the name
of its pass (`name`), which a device trace shows.

`tiles` and `tiles_by_group` return None for a shape the kernels do not
take (rows of room that no tile divides): the caller keeps XLA's kernel
there.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Tiles = Tuple[int, int, int]

# What a call's blocks may take of a v5e core's 128 MiB of VMEM, every
# block double-buffered (the compiler's default limit, 16 MiB, does not hold
# one expert's 2048 x 1792 twice).
VMEM_BUDGET = 48 * 2 ** 20
# Row tiles, best first. A group's end inside a tile costs that tile a second
# visit, so with `groups` ends a product of `rows / tile + groups` tile
# visits: 256 rows a tile wastes an eighth at 2048 rows a group where 512
# wastes a quarter, and a tile of 128 pays the grid's step twice as often.
ROW_TILES = (256, 128)
_LANES = 128


def _cut(extent: int, pieces: int) -> int:
    """The tile that cuts `extent` into `pieces`, a multiple of 128 lanes."""
    return -(-extent // pieces // _LANES) * _LANES if pieces > 1 else extent


def gmm_bytes(t: Tiles, itemsize: int = 2) -> int:
    """VMEM of `grouped`'s blocks at tiles `t` = (rows, contraction, width):
    rows, matrix and result twice each, and the float32 product (the
    accumulator where the contraction is cut)."""
    tm, tk, tn = t
    return 2 * itemsize * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn


def tgmm_bytes(t: Tiles, itemsize: int = 2) -> int:
    """VMEM of `grouped_by_group`'s blocks at tiles `t` = (rows, contraction
    of the forward product, width): both row blocks and the float32 result
    twice each."""
    tm, tk, tn = t
    return 2 * itemsize * tm * (tk + tn) + 2 * 4 * tk * tn


def _fitted(rows: int, contraction: int, width: int, need) -> Optional[Tiles]:
    """The first row tile that divides `rows`, with the width and the
    contraction cut into the fewest pieces that `need` fits into
    `VMEM_BUDGET`, the width before the contraction: a piece of the width
    is one more pass over the rows, a piece of the contraction has the
    group's matrix read again for every row tile."""
    tm = next((t for t in ROW_TILES if rows % t == 0), None)
    if tm is None:
        return None
    for pieces in range(1, 65):     # 64 pieces: an expert of 12 GB
        for across in range(pieces, 0, -1):
            if pieces % across:
                continue
            t = (tm, _cut(contraction, pieces // across), _cut(width, across))
            if need(t) <= VMEM_BUDGET:
                return t
    return None


def tiles(rows: int, contraction: int, width: int) -> Optional[Tiles]:
    """`grouped`'s tiles (rows, contraction, width) for `x [rows,
    contraction] @ w[e] [contraction, width]`, from the shape alone."""
    return _fitted(rows, contraction, width, gmm_bytes)


def tiles_by_group(rows: int, contraction: int,
                   width: int) -> Optional[Tiles]:
    """`grouped_by_group`'s tiles for `x [rows, contraction].T @ g [rows,
    width]`, from the shape alone."""
    return _fitted(rows, contraction, width, tgmm_bytes)


def _schedule(sizes, m: int, tm: int, empty_too: bool):
    """The grid steps over the row tiles, for the kernels' index maps (int32,
    prefetched): (`offsets` [groups + 1], a group's first row and the row
    past its last; the group [steps] and the row tile [steps] of every
    step) and how many steps there are. Group after group, each over the
    row tiles that hold rows of it: a tile that a group's end cuts is two
    steps running, no tile past the last group's end is any. `empty_too`:
    a group without rows gets one step (to write zeros), else none. At most
    `m // tm + groups - 1` steps; the entries past the last are in range
    and never run."""
    groups, row_tiles = sizes.shape[0], m // tm
    ends = jnp.cumsum(sizes)
    first = (ends - sizes) // tm
    visits = jnp.where(sizes > 0, (ends - 1) // tm - first + 1,
                       1 if empty_too else 0)
    upto = jnp.cumsum(visits)
    steps = jnp.arange(row_tiles + groups - 1, dtype=jnp.int32)
    group = jnp.minimum(jnp.sum(steps[:, None] >= upto[None, :], axis=1),
                        groups - 1)
    tile = jnp.minimum(first[group] + steps - (upto - visits)[group],
                       row_tiles - 1)
    offsets = jnp.concatenate([jnp.zeros(1, ends.dtype), ends])
    return tuple(v.astype(jnp.int32) for v in (offsets, group, tile)), upto[-1]


def _bounds(meta, step, tm: int):
    """Of the group that grid step `step` works on: its first row and the
    row after its last; the step's tile's first row; whether the tile has
    no other group's rows."""
    offsets, groups, row_tiles = meta
    start, end = offsets[groups[step]], offsets[groups[step] + 1]
    first = row_tiles[step] * tm
    return start, end, first, (start <= first) & (first + tm <= end)


def _mine(bounds, shape):
    """[tm, width] `shape`: whether the tile's row is the group's."""
    start, end, first, _ = bounds
    rows = first + lax.broadcasted_iota(jnp.int32, shape, 0)
    return (rows >= start) & (rows < end)


def _params(need: int):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=need + 8 * 2 ** 20)


@functools.partial(jax.jit, static_argnames=(
    "transposed", "tiling", "name", "interpret"))
def grouped(x, w, sizes, *, transposed: bool = False,
            tiling: Optional[Tiles] = None, name: str = "grouped_fwd",
            interpret: bool = False):
    """`x[rows of group e] @ w[e]` (`transposed`: `@ w[e].T`) as `x.dtype`,
    accumulated in float32. `x` [m, k]; `w` [groups, k, n] (`transposed`:
    [groups, n, k]) of `x.dtype`; `sizes` int32 [groups]. Rows past the
    last group's end are left unwritten."""
    (m, k), n = x.shape, w.shape[1 if transposed else 2]
    tm, tk, tn = tiling or tiles(m, k, n)
    tiles_k, k_rem = pl.cdiv(k, tk), k % tk
    meta, visits = _schedule(sizes, m, tm, False)

    def kernel(meta, x_ref, w_ref, out_ref, *acc):
        step, k_i = pl.program_id(1), pl.program_id(2)

        def product(last: bool):
            a, b = x_ref[...], w_ref[...]
            if last and k_rem:
                # the last piece of the contraction reaches past its end
                a = jnp.where(lax.broadcasted_iota(
                    jnp.int32, a.shape, 1) < k_rem, a, jnp.zeros((), a.dtype))
                b = jnp.where(lax.broadcasted_iota(
                    jnp.int32, b.shape, 1 if transposed else 0) < k_rem,
                    b, jnp.zeros((), b.dtype))
            return lax.dot_general(
                a, b, (((1,), (1 if transposed else 0,)), ((), ())),
                preferred_element_type=jnp.float32)

        def store(y):
            # the rows of other groups keep what an earlier visit wrote
            out_ref[...] = jnp.where(
                _mine(_bounds(meta, step, tm), y.shape), y,
                out_ref[...].astype(jnp.float32)).astype(out_ref.dtype)

        if tiles_k == 1:
            store(product(True))
            return
        acc, = acc

        @pl.when(k_i == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)

        @pl.when(k_i < tiles_k - 1)
        def _():
            acc[...] += product(False)

        @pl.when(k_i == tiles_k - 1)
        def _():
            store(acc[...] + product(True))

    def x_block(n_i, step, k_i, meta):
        return meta[2][step], k_i

    def w_block(n_i, step, k_i, meta):
        return (meta[1][step],) + ((n_i, k_i) if transposed else (k_i, n_i))

    def out_block(n_i, step, k_i, meta):
        return meta[2][step], n_i

    call = pl.pallas_call(
        kernel, name=name, interpret=interpret,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[
                pl.BlockSpec((tm, tk), x_block),
                pl.BlockSpec((None,) + ((tn, tk) if transposed else (tk, tn)),
                             w_block)],
            out_specs=pl.BlockSpec((tm, tn), out_block),
            grid=(pl.cdiv(n, tn), visits, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)][:tiles_k - 1]),
        compiler_params=_params(gmm_bytes((tm, tk, tn), x.dtype.itemsize)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=x.dtype.itemsize * (m * k + w.size + m * n)))
    return call(meta, x, w)


@functools.partial(jax.jit, static_argnames=("tiling", "name", "interpret"))
def grouped_by_group(x, g, sizes, *, tiling: Optional[Tiles] = None,
                     name: str = "grouped_dw", interpret: bool = False):
    """`x[rows of group e].T @ g[rows of group e]` for every group, float32
    [groups, k, n]: zeros for a group without rows. `x` [m, k], `g` [m, n],
    `sizes` int32 [groups]."""
    (m, k), n, groups = x.shape, g.shape[1], sizes.shape[0]
    tm, tk, tn = tiling or tiles_by_group(m, k, n)
    meta, visits = _schedule(sizes, m, tm, True)

    def kernel(meta, x_ref, g_ref, out_ref):
        step = pl.program_id(2)
        group = meta[1][step]
        before = meta[1][jnp.maximum(step - 1, 0)]

        @pl.when((step == 0) | (group != before))
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)

        bounds = _bounds(meta, step, tm)

        def add(masked: bool):
            a, b = x_ref[...], g_ref[...]
            if masked:
                a, b = (jnp.where(_mine(bounds, v.shape),
                                  v.astype(jnp.float32), 0.0).astype(v.dtype)
                        for v in (a, b))
            out_ref[...] += lax.dot_general(
                a, b, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        start, end, _, whole = bounds
        pl.when(whole)(functools.partial(add, False))
        pl.when((end > start) & jnp.logical_not(whole))(
            functools.partial(add, True))

    call = pl.pallas_call(
        kernel, name=name, interpret=interpret,
        out_shape=jax.ShapeDtypeStruct((groups, k, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, k_i, step, meta: (
                    meta[2][step], k_i)),
                pl.BlockSpec((tm, tn), lambda n_i, k_i, step, meta: (
                    meta[2][step], n_i))],
            out_specs=pl.BlockSpec(
                (None, tk, tn), lambda n_i, k_i, step, meta: (
                    meta[1][step], k_i, n_i)),
            grid=(pl.cdiv(n, tn), pl.cdiv(k, tk), visits)),
        compiler_params=_params(tgmm_bytes((tm, tk, tn), x.dtype.itemsize)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(x.dtype.itemsize * m * (k + n)
                            + 4 * groups * k * n)))
    return call(meta, x, g)
