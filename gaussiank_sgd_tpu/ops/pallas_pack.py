"""Pallas fused threshold-select + pack kernel — packed (index, value) pairs.

Reference parity: the north-star deliverable (BASELINE.json ``north_star``,
SURVEY.md §7 stage 6): the reference's ``GaussianCompressor`` select+pack
(``compression.py``) re-built as a TPU kernel that *emits packed (index,
value) pairs* instead of composing XLA sort/select primitives.

Why it exists (measured in r3, CHANGELOG_r4.md; the artifact is gone): at 57M
params the XLA pack (`abs` + bf16 key + ``lax.approx_max_k`` + gather) costs
6.5-8.6 ms — ~3-4x over raw HBM-bandwidth theory, and the dominant term of
the whole sparse-step overhead. A threshold select is informationally one
pass: read each element once, keep the few that cross ``t``. The obstacle on
TPU is *compaction* — the VPU has no efficient scatter, so "move the selected
entries to the front" is the expensive part, and an n-sized XLA scatter
serializes (~93 ms at 15M, r3 memory). This kernel solves compaction with a
TPU-shaped two-level scheme:

  1. **In-kernel (one HBM pass)**: the flat buffer is viewed as
     ``[rows, 128]`` and gridded into blocks of ``R`` rows; inside a block
     the rows regroup into SEGMENTS of ``SEG`` rows, and the kernel emits
     the single largest above-threshold entry of every (segment, lane)
     cell — ONE segmented max-reduction over an int32 ranking key instead
     of a sequential extraction loop. (The r4 kernel pulled top-8 per
     column via 8 dependent max/mask/sum rounds — ~35 vector passes per
     block; profiling in r5 showed that loop VPU-bound at ~4.8 ms at 57M,
     6x the pure HBM read. The segmented form is ~8 passes, measured
     1.8 ms.) The key is the f32 magnitude's bit pattern with its low
     log2(SEG) mantissa bits replaced by the row-in-segment — order-
     preserving to ~2^-(23-log2(SEG)) relative, unique within the cell, so
     the winner falls out of one max and its row decodes from the key's
     low bits. The exact f32 value is recovered by a masked segment-sum
     over the winner's one-hot. HBM traffic is exactly one read of the
     buffer plus the (tiny) candidate tiles.
  2. **In-XLA (small)**: the candidate buffer has ``nc = n/SEG`` slots
     (64x smaller than the gradient at the contract density), of which
     about k hold a candidate. The k of largest magnitude are picked in
     f32 WITHOUT a sort (``_cand_top_k``): the k-th magnitude by 31
     counting passes over the buffer, the slots at or above it ranked in
     position order by prefix counts, and the k pairs read by a k-sized
     row gather. Exactly ``lax.top_k``'s set; what it passes over (only
     ever candidates below the k-th magnitude) stays in the residual.

The fused **EF+select** form (``_ef_select_kernel`` /
``gaussian_fused_ef_compress_batched``) additionally folds the error-
feedback accumulate into the same HBM pass: the kernel reads the carried
residual and the new gradient, writes ``acc = residual + scale*grad``, and
emits the candidates of that acc — 3 n-sized transfers per step (read res,
read grad, write acc) instead of the 5+ of the unfused
accumulate-then-select pipeline. It requires the caller to keep a
PRE-PADDED live EF buffer (chunks block-aligned via ``ef_padded_chunk``)
so the kernel pass needs no ``jnp.pad`` copy; padding is stripped at the
checkpoint/elastic edges (training/checkpoint.py). The pad region is
provably inert: thresholds are always >= 0, the select mask is strict
``|x| > t``, and the pad starts (and therefore stays) zero, so no pad
element is ever selected and the residual pad remains zero forever.

Selection contract vs ``pack_by_mask(priority="magnitude")``: identical mask
(``|acc| > t``), identical exact EF bookkeeping (the caller zeroes exactly
the k sent entries; everything else — including any entry beyond a cell's
one-slot cap — stays in the residual and is re-selected next step). ``SEG``
shrinks with density so the per-cell above-threshold count lambda =
SEG*density stays <= ~0.5: cap overflow P(X>=2|lambda) <= ~9% of cells at
the ceiling, ~0.2% at the contract density; overflow loses nothing (EF),
it only defers.

``num_selected`` is the exact above-threshold count, accumulated in SMEM
across the (sequential) grid — the same observability the reference logs.

Execution mode: a train step binds ``interpret`` explicitly from the
platform of the mesh it is built for (parallel/trainstep.py — a TPU mesh
always gets the Mosaic-compiled kernel, a CPU mesh the interpreter, and
``DPTrainStep.kernel_mode`` says which). ``interpret=None`` is for direct
library calls outside a step, which run on the process's default backend.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from ..compressors.base import (CompressedGrad, CompressResult,
                                finish_pack)

_LANES = 128
_MAX_SEG = 64     # largest segment span (contract-density geometry, n/64
                  # candidates); shrinks with density — see segment_span
_DENSITY_CEIL = 1.0 / 32   # capacity ceiling (unchanged from r4's S/R)
_SUBLANES = 8     # Mosaic tiles f32/i32 as (8, 128): every block's
                  # second-to-last dim must be a multiple of 8


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Pallas execution mode for a DIRECT kernel call: ``None`` means "the
    process's default backend" (arrays created outside a mesh live there).
    Train steps never pass None — see the module docstring."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def segment_span(density: float) -> int:
    """SEG: rows per one-slot candidate cell, by density.

    Capacity is 1/SEG of n, so SEG must satisfy ``density <= 1/SEG``; the
    chosen rule ``SEG*density <= 0.5`` keeps >= 2x headroom for the warm
    controller's count band and bounds cap overflow P(X>=2 | lambda) at
    ~9% of cells (lambda 0.5) worst case, ~0.2% at the contract density
    (lambda 0.064)."""
    seg = _MAX_SEG
    while seg > 8 and density * seg > 0.5:
        seg //= 2
    return seg


def rows_per_block(density: float) -> int:
    """Grid-block span R (rows per grid step) — a VMEM budget, not a
    statistics choice (segmentation handles density now; R just sets how
    much of the buffer is resident per step). [1024,128] f32 + i32 key +
    intermediates keep ~3 MB live — comfortable double-buffering headroom
    inside the ~16 MB VMEM."""
    if not supports_density(density):
        raise ValueError(
            f"fused select+pack supports density <= {_DENSITY_CEIL}, "
            f"got {density}")
    return 1024


def supports_density(density: float) -> bool:
    """True iff the kernel geometry can emit k = density*n pairs.

    At the 1/32 ceiling the SEG=16 geometry holds 1/16 of n candidate
    slots >= k. Beyond it the registry builds the XLA warm selector under
    its own name (``gaussian_fused(warm-fallback)``); the kernel entry
    points here refuse such a call (:func:`_require_capacity`)."""
    return density <= _DENSITY_CEIL


def _require_capacity(chunk: int, k: int, density: float) -> None:
    """The geometry gate of the select+pack entry points: raise unless the
    kernel can emit ``k`` pairs from a ``chunk``-element buffer. There is
    no quiet route to another selector under this one's name — the
    registry picks (and names) the selector from the density before a step
    is built, and k = ceil(density*chunk) always fits below the ceiling
    (capacity is >= 2*density*chunk). Above the density ceiling
    ``rows_per_block`` raises from inside ``_chunk_geometry``."""
    nc = _chunk_geometry(chunk, density)[3]
    if k > nc:
        raise ValueError(f"k={k} exceeds candidate capacity {nc} "
                         f"(chunk={chunk}, density={density})")


def _chunk_geometry(chunk: int,
                    density: float) -> Tuple[int, int, int, int]:
    """(R, SEG, blocks_per_chunk, candidate_capacity) for a chunk of
    ``chunk`` elements at ``density`` — the single source of the geometry
    rules so capacity checks agree with what the kernel actually runs.

    Every geometry returned here is one Mosaic accepts: the candidate
    tile is ``[R // SEG, 128]``, so ``R`` is a multiple of ``8 * SEG``
    (tests/test_kernel_lowering.py lowers the grid for TPU).

    A chunk smaller than one 1024-row block gets ONE block of its own
    rows, rounded up to that ``8 * SEG`` granule — without the cap a
    uniform plan's small chunks would zero-pad to a full block and the
    kernel's HBM pass would read mostly zeros. SEG halves (more candidate
    cells, so never less capacity or more cap overflow than the density
    rule asked for) until the granule fits the chunk, which bounds the pad
    below 2x down to 8192-element chunks."""
    R = rows_per_block(density)
    seg = segment_span(density)
    rows_total = -(-chunk // _LANES)
    if rows_total < R:
        while seg > 8 and _SUBLANES * seg > rows_total:
            seg //= 2
        granule = _SUBLANES * seg
        R = -(-rows_total // granule) * granule
    bpc = -(-chunk // (R * _LANES))
    return R, seg, bpc, (R // seg) * bpc * _LANES


def _select_kernel(x_ref, t_ref, val_ref, idx_ref, count_ref, *,
                   rows: int, seg: int):
    """One grid step: the largest above-threshold entry per (segment, lane).

    Grid is ``(n_chunks, blocks_per_chunk)`` — the chunk axis is what makes
    the kernel compatible with uniform bucket plans (VERDICT r4 item 3: the
    default selector must keep its kernel at exactly the scale where
    uniform plans become necessary). The single-buffer path is the
    ``n_chunks == 1`` special case of the same program. Emitted flat
    indices are CHUNK-LOCAL (``base`` restarts at every chunk), matching
    the batched-compressor convention of parallel/trainstep.py
    ``compress_buckets`` (the caller offsets per chunk).

    x_ref: [R, 128] f32 block of this chunk's buffer view.
    t_ref: [n_chunks, 1] f32 — ALL thresholds in SMEM (whole-array block:
    Mosaic requires SMEM block shapes to equal the array dims; the kernel
    picks its chunk's row by ``program_id(0)``).
    val_ref/idx_ref: [R//seg, 128] candidate tiles for this block.
    count_ref: [n_chunks, 1] i32 SMEM accumulator (exact above-threshold
    count), one row per chunk, carried across the chunk's sequential
    blocks.
    """
    x = x_ref[:]
    _emit_candidates(x, t_ref, val_ref, idx_ref, count_ref,
                     rows=rows, seg=seg)


def _ef_select_kernel(res_ref, g_ref, scale_ref, t_ref,
                      acc_ref, val_ref, idx_ref, count_ref, *,
                      rows: int, seg: int):
    """The fused EF+select grid step: acc = res + scale*grad, candidates of
    that acc — one HBM pass over both n-sized inputs and the n-sized output.

    Identical candidate contract to :func:`_select_kernel` (shared body,
    ``_emit_candidates``); the only addition is the EF accumulate. The
    caller persists ``acc_ref`` as the NEW EF buffer and later zeroes the
    k sent entries (finish_pack), exactly as in the unfused path.

    res_ref/g_ref/acc_ref: [R, 128] f32 blocks (grad pre-cast by the
    wrapper — the kernel is f32-only, matching the accumulate dtype).
    scale_ref: [1, 1] f32 SMEM — the grad scale (folded LR or 1).
    """
    acc = res_ref[:] + scale_ref[0, 0] * g_ref[:]
    acc_ref[:] = acc
    _emit_candidates(acc, t_ref, val_ref, idx_ref, count_ref,
                     rows=rows, seg=seg)


def _emit_candidates(x, t_ref, val_ref, idx_ref, count_ref, *,
                     rows: int, seg: int):
    """Candidate-emission body shared by the select-only and EF+select
    kernels: largest above-threshold entry per (segment, lane) of the
    in-register block ``x``, plus the exact above-threshold count."""
    c = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        count_ref[c, 0] = 0

    ax = jnp.abs(x)
    t = t_ref[c, 0]
    mask = ax > t
    count_ref[c, 0] += jnp.sum(mask.astype(jnp.int32))

    nseg = rows // seg
    seg_mask = seg - 1
    rowid = lax.broadcasted_iota(jnp.int32, (rows, _LANES), 0) & seg_mask
    # int32 ranking key: positive-f32 bit pattern (int compare == float
    # compare for non-negative floats), low log2(seg) bits replaced by the
    # row-in-segment so every in-cell key is unique. 0 = "not selected"
    # sentinel; a selected element whose magnitude bits round to 0
    # (subnormal ~<1e-43) would collide with the sentinel and stay in the
    # residual — harmless.
    bits = lax.bitcast_convert_type(ax, jnp.int32)
    key = jnp.where(mask, (bits & ~seg_mask) | rowid, 0)

    key3 = key.reshape(nseg, seg, _LANES)
    top = jnp.max(key3, axis=1)                            # [nseg, 128]
    valid = top > 0
    win = (key3 == top[:, None, :]) & valid[:, None, :]    # one-hot per cell
    # exact f32 value via the winner's one-hot (the key itself only keeps
    # the top 23-log2(seg) magnitude bits)
    val = jnp.sum(jnp.where(win, x.reshape(nseg, seg, _LANES), 0.0), axis=1)
    base = i * rows  # first CHUNK-LOCAL flat row of this block
    seg_row = (base
               + lax.broadcasted_iota(jnp.int32, (nseg, _LANES), 0) * seg
               + (top & seg_mask))
    lane = lax.broadcasted_iota(jnp.int32, (nseg, _LANES), 1)
    flat_idx = seg_row * _LANES + lane
    val_ref[:] = jnp.where(valid, val, 0.0)
    idx_ref[:] = jnp.where(valid, flat_idx, 0)


def fused_select_candidates_chunked(
    x2d: jax.Array, thresholds: jax.Array, density: float,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Kernel pass over ``[n_chunks, chunk]`` with PER-CHUNK thresholds.

    Returns ``(cand_values [n_chunks, nc], cand_indices [n_chunks, nc]
    CHUNK-LOCAL, counts [n_chunks])``. One ``pallas_call`` whose grid's
    leading axis is the chunk — compile time and HLO size are O(1) in
    chunk count, the property uniform bucket plans exist for
    (parallel/bucketing.py). Each chunk is zero-padded to a block multiple
    (zeros never cross a positive threshold; the pad region is beyond every
    valid chunk-local index, so residual stripping is unaffected).
    """
    interpret = resolve_interpret(interpret)
    n_chunks, chunk = x2d.shape
    R, seg, bpc, nc = _chunk_geometry(chunk, density)
    nseg = R // seg
    block = R * _LANES
    chunk_pad = bpc * block
    x = jnp.pad(x2d.astype(jnp.float32),
                ((0, 0), (0, chunk_pad - chunk))).reshape(-1, _LANES)

    space = None if interpret else pltpu.VMEM
    smem = None if interpret else pltpu.SMEM
    vals, idxs, counts = pl.pallas_call(
        functools.partial(_select_kernel, rows=R, seg=seg),
        name="select",
        grid=(n_chunks, bpc),
        in_specs=[
            pl.BlockSpec((R, _LANES), lambda c, i: (c * bpc + i, 0),
                         memory_space=space),
            # whole-array SMEM blocks (Mosaic: block dims must equal the
            # array dims for non-(8,128)-divisible shapes); the kernel
            # indexes its chunk's row by program_id(0)
            pl.BlockSpec((n_chunks, 1), lambda c, i: (0, 0),
                         memory_space=smem),
        ],
        out_specs=(
            pl.BlockSpec((nseg, _LANES), lambda c, i: (c * bpc + i, 0),
                         memory_space=space),
            pl.BlockSpec((nseg, _LANES), lambda c, i: (c * bpc + i, 0),
                         memory_space=space),
            pl.BlockSpec((n_chunks, 1), lambda c, i: (0, 0),
                         memory_space=smem),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n_chunks * bpc * nseg, _LANES),
                                 jnp.float32),
            jax.ShapeDtypeStruct((n_chunks * bpc * nseg, _LANES),
                                 jnp.int32),
            jax.ShapeDtypeStruct((n_chunks, 1), jnp.int32),
        ),
        interpret=interpret,
    )(x, thresholds.astype(jnp.float32).reshape(n_chunks, 1))
    # rows of the output tiles are (chunk, block, segment) — contiguous per
    # chunk, so the per-chunk candidate list is a plain reshape
    return (vals.reshape(n_chunks, nc), idxs.reshape(n_chunks, nc),
            counts[:, 0])


def ef_padded_chunk(chunk: int, k: int, *,
                    density: float) -> Optional[int]:
    """Block-aligned chunk size the fused EF+select kernel needs, or None
    when the fused-EF path cannot serve this (chunk, k, density).

    The fused kernel keeps the EF buffer PRE-PADDED so its HBM pass needs
    no copy: each chunk's live size must be ``blocks_per_chunk * R * 128``.
    For a single whole-model bucket that is a pure suffix pad; a uniform
    plan is eligible iff its chunk is already block-aligned (returned value
    == chunk) — otherwise the in-chunk pad would shift every following
    chunk's global offsets and the caller must keep the unfused path.

    Returns None (the step keeps the unfused EF accumulate) when the
    density is above the geometry ceiling or k exceeds the candidate
    capacity — the conditions :func:`_require_capacity` refuses."""
    if not supports_density(density):
        return None
    R, _, bpc, nc = _chunk_geometry(chunk, density)
    if k > nc:
        return None
    return bpc * R * _LANES


def fused_ef_select_candidates_chunked(
    res2d: jax.Array, g2d: jax.Array, scale: jax.Array,
    thresholds: jax.Array, density: float,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused EF accumulate + candidate pass over pre-padded
    ``[n_chunks, chunk_pad]`` buffers with PER-CHUNK thresholds.

    Returns ``(acc2d [n_chunks, chunk_pad], cand_values [n_chunks, nc],
    cand_indices [n_chunks, nc] CHUNK-LOCAL, counts [n_chunks])`` where
    ``acc2d = res2d + scale * g2d`` is the new (unzeroed) EF accumulator.
    Unlike :func:`fused_select_candidates_chunked` the inputs must already
    be block-aligned (``chunk_pad == ef_padded_chunk(...)``) — there is no
    ``jnp.pad`` here, which is the point: the pad copy the unfused path
    pays every step is exactly what fusion removes.
    """
    interpret = resolve_interpret(interpret)
    n_chunks, chunk_pad = res2d.shape
    R, seg, bpc, nc = _chunk_geometry(chunk_pad, density)
    nseg = R // seg
    if bpc * R * _LANES != chunk_pad:
        raise ValueError(
            f"fused EF path needs block-aligned chunks: chunk_pad="
            f"{chunk_pad} != {bpc}*{R}*{_LANES}; pad the live EF buffer "
            f"with ef_padded_chunk first")
    res = res2d.astype(jnp.float32).reshape(-1, _LANES)
    g = g2d.astype(jnp.float32).reshape(-1, _LANES)
    scale2d = jnp.asarray(scale, jnp.float32).reshape(1, 1)

    space = None if interpret else pltpu.VMEM
    smem = None if interpret else pltpu.SMEM
    acc, vals, idxs, counts = pl.pallas_call(
        functools.partial(_ef_select_kernel, rows=R, seg=seg),
        name="ef_select",
        grid=(n_chunks, bpc),
        in_specs=[
            pl.BlockSpec((R, _LANES), lambda c, i: (c * bpc + i, 0),
                         memory_space=space),
            pl.BlockSpec((R, _LANES), lambda c, i: (c * bpc + i, 0),
                         memory_space=space),
            pl.BlockSpec((1, 1), lambda c, i: (0, 0), memory_space=smem),
            pl.BlockSpec((n_chunks, 1), lambda c, i: (0, 0),
                         memory_space=smem),
        ],
        out_specs=(
            pl.BlockSpec((R, _LANES), lambda c, i: (c * bpc + i, 0),
                         memory_space=space),
            pl.BlockSpec((nseg, _LANES), lambda c, i: (c * bpc + i, 0),
                         memory_space=space),
            pl.BlockSpec((nseg, _LANES), lambda c, i: (c * bpc + i, 0),
                         memory_space=space),
            pl.BlockSpec((n_chunks, 1), lambda c, i: (0, 0),
                         memory_space=smem),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n_chunks * bpc * R, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((n_chunks * bpc * nseg, _LANES),
                                 jnp.float32),
            jax.ShapeDtypeStruct((n_chunks * bpc * nseg, _LANES),
                                 jnp.int32),
            jax.ShapeDtypeStruct((n_chunks, 1), jnp.int32),
        ),
        interpret=interpret,
    )(res, g, scale2d, thresholds.astype(jnp.float32).reshape(n_chunks, 1))
    return (acc.reshape(n_chunks, chunk_pad),
            vals.reshape(n_chunks, nc), idxs.reshape(n_chunks, nc),
            counts[:, 0])


def fused_select_candidates(
    acc: jax.Array, threshold: jax.Array, density: float,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One kernel pass: (cand_values [nc], cand_indices [nc], count).

    ``acc`` is the flat f32 EF accumulator; candidates are the largest
    above-threshold entry of each (SEG-row, lane) cell (module docstring).
    Invalid slots hold (value 0, index 0). The single-buffer form is the
    ``n_chunks == 1`` case of :func:`fused_select_candidates_chunked`
    (chunk-local index == global flat index).
    """
    vals, idxs, counts = fused_select_candidates_chunked(
        acc[None, :], threshold.reshape(1), density, interpret)
    return vals[0], idxs[0], counts[0]


def _lane_prefix(mask: jax.Array, *, inclusive: bool) -> jax.Array:
    """How many True lanes lie before (or at) each lane of its row, for a
    ``[rows, 128]`` mask: 0/1 in bf16 times a triangle of ones on the MXU
    with f32 sums — exact, a row holds at most 128."""
    ones = jnp.ones((_LANES, _LANES), jnp.bfloat16)
    tri = jnp.triu(ones, 0 if inclusive else 1)
    return jnp.dot(mask.astype(jnp.bfloat16), tri,
                   preferred_element_type=jnp.float32).astype(jnp.int32)


def _kth_key(key: jax.Array, k: int) -> jax.Array:
    """The k-th largest of the int32 ranking keys: the largest ``t`` with
    ``count(key >= t) >= k``, or 1 when fewer than k keys are positive
    (every valid slot then passes ``key >= t``). Built bit by bit from the
    top in 31 counting passes over the buffer — non-negative floats order
    as their bit patterns, which the kernel's key already relies on."""
    t = jnp.zeros((), jnp.int32)
    for bit in range(30, -1, -1):
        cand = t | (1 << bit)
        t = jnp.where(jnp.sum(key >= cand) >= k, cand, t)
    return jnp.maximum(t, 1)


def _cand_top_k(vals: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """Where the k largest candidate magnitudes sit, over the ``[nc/128,
    128]`` view the kernel wrote: ``(rank [nc/128, 128], row [k])``.
    ``rank[r, l]`` counts the chosen slots at or before ``(r, l)`` in
    row-major order, so the j-th chosen slot is the first position of row
    ``row[j]`` whose rank exceeds j, and an output slot past the last
    chosen one finds none (:func:`_read_slots`). The chosen slots are
    exactly the set ``lax.top_k(|vals|, k)`` picks (ties at the k-th
    magnitude go to the lower position, as there), zeros never.

    No sort. About k of the nc slots hold a candidate and the order of the
    k pairs is free, so ranking all nc is wasted work — and dear: under the
    batched forms' ``vmap`` the sort's operand is ``[1, nc]``, which the
    chip sorts ten times slower than a flat ``[nc]`` (3.47 ms a step at
    nc = 399 360, the longest operation of ResNet-50's whole step; PERF.md
    section 6, PR 30). Instead: the k-th magnitude by counting
    (:func:`_kth_key`), the slots at or above it ranked in position order
    by prefix counts (lanes on the MXU, rows by a cumulative sum), and the
    row of every output slot from the row totals. One path for every
    (nc, k): flat, batched, cold start, a dead bucket."""
    with jax.named_scope("cand_topk"):
        rows = vals.shape[0] // _LANES
        key = lax.bitcast_convert_type(
            jnp.abs(vals), jnp.int32).reshape(rows, _LANES)
        t = _kth_key(key, k)
        above = key > t
        tied = key == t
        # of the slots tied at the k-th magnitude, the first in position
        # order fill what `above` leaves of k
        room = k - jnp.sum(above)
        tied_in_row = jnp.sum(tied, axis=1)
        tied_before = ((jnp.cumsum(tied_in_row) - tied_in_row)[:, None]
                       + _lane_prefix(tied, inclusive=False))
        chosen = above | (tied & (tied_before < room))
        chosen_in_row = jnp.sum(chosen, axis=1)
        through_row = jnp.cumsum(chosen_in_row)
        rank = ((through_row - chosen_in_row)[:, None]
                + _lane_prefix(chosen, inclusive=True))
        # row of slot j = how many rows' running totals are <= j: a mark
        # per row at its total (an nc/128-sized scatter), summed along j
        row_ends = jnp.zeros((k,), jnp.int32).at[through_row].add(
            1, mode="drop", indices_are_sorted=True)
        row = jnp.minimum(jnp.cumsum(row_ends), rows - 1)
        return rank, row


# output slots read per pass of _read_slots: three [block, 128] int32 row
# gathers live at once (50 MB) whatever k is
_SLOT_BLOCK = 1 << 15


def _read_slots(rank: jax.Array, bits: jax.Array, idxs: jax.Array,
                row: jax.Array, j: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(value bits, index) of output slots ``j`` (rows ``row``) from the
    ``[nc/128, 128]`` views: each slot gathers its ROW of ranks, finds its
    lane by comparing them with j, and picks that lane of the row's values
    and indices. A 128-lane row gather costs the chip a third of a scalar
    gather from the flat buffer. A slot past the last chosen one finds no
    lane and reads (0, 0)."""
    with jax.named_scope("cand_topk"):
        lane = jnp.sum(rank[row] <= j[:, None], axis=1)
    with jax.named_scope("pack"):
        hit = (lax.broadcasted_iota(jnp.int32, (j.shape[0], _LANES), 1)
               == lane[:, None])
        return (jnp.sum(jnp.where(hit, bits[row], 0), axis=1),
                jnp.sum(jnp.where(hit, idxs[row], 0), axis=1))


def _select_candidates_topk(vals: jax.Array, idxs: jax.Array, k: int,
                            n: int) -> Tuple[jax.Array, jax.Array]:
    """The selection half of the fused pack: ``(sent_idx [k], val [k])``
    with the out-of-range sentinel ``n`` on invalid slots (|val| > 0
    validity rule: a selected subnormal whose key rounds to the 0 sentinel
    stays in the residual). Small outputs only, so stateful wrappers can
    route the result through a ``lax.cond`` without paying the big-buffer
    cond-boundary copy (see base.select_by_mask). Values travel as bits:
    what is sent is the kernel's float32 bit for bit."""
    rank, row = _cand_top_k(vals, k)
    with jax.named_scope("pack"):
        read = functools.partial(
            _read_slots, rank,
            lax.bitcast_convert_type(vals, jnp.int32).reshape(rank.shape),
            idxs.reshape(rank.shape))
        j = jnp.arange(k, dtype=jnp.int32)
        if k <= _SLOT_BLOCK:
            bits, idx = read(row, j)
        else:       # block by block, so the row gathers stay small
            pad = -k % _SLOT_BLOCK
            blocks = [jnp.pad(a, (0, pad), constant_values=c).reshape(
                -1, _SLOT_BLOCK) for a, c in ((row, 0), (j, k))]
            bits, idx = (a.reshape(-1)[:k] for a in
                         lax.map(lambda rj: read(*rj), tuple(blocks)))
        val = lax.bitcast_convert_type(bits, jnp.float32)
        valid = jnp.abs(val) > 0      # an unfilled slot read (0, 0)
        val = jnp.where(valid, val, 0.0)
        sent_idx = jnp.where(valid, idx, n).astype(jnp.int32)
    return sent_idx, val


# the share of Newton's step that the controller takes where the sent
# magnitudes lie close together (see _controller_update)
_SPREAD_DAMPING = 0.7


def _controller_update(state: jax.Array, count: jax.Array, val: jax.Array,
                       valid: jax.Array, k: int, gain: float) -> jax.Array:
    """Next carried threshold (shared by the flat and batched fused forms).

    Warm (state > 0): a multiplicative step toward count == k, clipped to
    [1/4, 4]: ``(count / k) ** g``. Where the sent magnitudes lie apart,
    g is ``gain`` — the controller of gaussian_warm_compress. Where they
    lie close together, a step of that size overshoots: with the sent
    magnitudes a mean share ``spread`` above the smallest of them, the
    count falls by about ``1 / spread`` in the logarithm for every unit the
    threshold rises, so Newton's step to count == k is ``(count / k) **
    spread``, and ``gain`` is many times that where one large population of
    like entries sits just under the threshold under error feedback (96 M
    of `joyai_llm_flash`'s 414 M: 19 k selected, the threshold times 1.7,
    NOTHING selected, the threshold times 1/4, 120 k selected, and round
    again; PERF.md section 6, PR 33). So g is the smaller of ``gain`` and
    ``_SPREAD_DAMPING * spread``, and, in the measure that g falls short
    of ``gain`` and where all k slots were filled, the step starts from the
    smallest SENT magnitude — the threshold that this step's candidates
    would have met exactly — and not from the carried one. ``spread`` is
    read above that magnitude where the slots were filled and above the
    carried threshold where not (one outlier alone above the threshold
    has no spread of its own, and the threshold still has to come down).
    Cold (state <= 0): adopt the smallest SENT magnitude — the k-th
    largest candidate, a free near-ideal threshold estimate (see
    gaussian_fused_compress docstring). An all-invalid selection (dead
    bucket) bootstraps to a tiny positive value so the controller can
    re-raise it multiplicatively when gradients appear.
    """
    with jax.named_scope("ef_select"):
        ratio = (count.astype(jnp.float32) + 1.0) / float(k + 1)
        mag = jnp.abs(val.astype(jnp.float32))
        kth = jnp.min(jnp.where(valid, mag, jnp.inf), axis=-1)
        sent = jnp.sum(valid.astype(jnp.float32), axis=-1)
        mean = jnp.sum(jnp.where(valid, mag, 0.0), axis=-1) / jnp.maximum(
            sent, 1.0)
        warm = jnp.where(state > 0, state, 1.0)
        # what the sent magnitudes lie above: the k-th of them where all
        # slots were filled, else the threshold that let them through
        filled = jnp.all(valid, axis=-1)
        base = jnp.where(filled, kth, warm)
        # nothing sent: nothing to read a spread from, the plain step
        spread = jnp.where(sent > 0, jnp.maximum(mean / base - 1.0, 0.0),
                           jnp.inf)
        g = jnp.minimum(gain, _SPREAD_DAMPING * spread)
        t_warm = (warm * jnp.clip(ratio ** g, 0.25, 4.0)
                  * (base / warm) ** (1.0 - g / gain))
        bootstrap = jnp.where(jnp.isfinite(kth), kth, jnp.float32(1e-8))
        return jnp.where(state > 0, t_warm, bootstrap).astype(state.dtype)


def _pack_candidates(vals: jax.Array, idxs: jax.Array, buf: jax.Array,
                     k: int) -> Tuple[CompressedGrad, jax.Array]:
    """Top-k pack of a candidate buffer against ``buf`` (the chunk the
    candidates came from): (CompressedGrad, EF residual). The shared tail
    of every fused path — ONE copy so the validity rule and the drop-mode
    EF zeroing can never diverge between the flat and batched forms
    (code-review r5)."""
    sent_idx, val = _select_candidates_topk(vals, idxs, k, buf.shape[0])
    return finish_pack(buf, sent_idx, val.astype(buf.dtype))


def fused_select_pack(acc: jax.Array, k: int, threshold: jax.Array,
                      density: float,
                      interpret: Optional[bool] = None) -> CompressResult:
    """Threshold-select ``|acc| > threshold`` packed to exactly k pairs.

    Drop-in for ``pack_by_threshold`` (same CompressResult contract: exactly
    k slots, (0, 0) padding, exact EF residual) with the selection done by
    the fused kernel + an exact f32 top-k over the small candidate buffer.
    Truncation beyond k drops smallest-magnitude candidates — the
    ``pack_by_mask(priority="magnitude")`` contract.
    """
    _require_capacity(acc.shape[0], k, density)
    with jax.named_scope("ef_select"):
        vals, idxs, count = fused_select_candidates(acc, threshold, density,
                                                    interpret)
    comp, residual = _pack_candidates(vals, idxs, acc, k)
    return CompressResult(comp, residual, count)


def gaussian_fused_compress(acc: jax.Array, k: int, state: jax.Array,
                            rng: Optional[jax.Array] = None,
                            *, density: float = 0.001,
                            sigma_scale: Optional[float] = None,
                            gain: float = 0.18,
                            interpret: Optional[bool] = None,
                            ) -> Tuple[CompressResult, jax.Array]:
    """Warm-threshold GaussianK with the fused Pallas select+pack — and NO
    branches on the hot path.

    Stateful contract matches ``gaussian_warm_compress``
    (compressors/gaussian.py): the threshold is carried across steps and a
    multiplicative controller nudges it toward count == k. The r5 redesign
    removes the cold-start/recovery ``lax.cond`` entirely (measured: ANY
    conditional carrying the n-sized cold computation costs ~1 extra HBM
    pass per step at 57M even when never taken):

      * every step is the SAME three-op program: kernel candidate
        extraction -> small top-k -> finish_pack;
      * cold start (state <= 0): the kernel's mask ``|x| > t`` at t <= 0
        passes everything, so the candidates are exactly the per-cell
        maxima and the top-k of THOSE is already a near-exact first
        selection (collision losses ~3% at contract shapes, EF-deferred).
        The k-th candidate magnitude — free from the top-k we just ran —
        is then a near-ideal threshold, adopted as the next state: one
        step to fully warm, no Gaussian estimate, no bisection;
      * band exits (count drifted from k): the clipped multiplicative
        update (x4 per step max) walks back in O(log) steps; meanwhile
        selection degrades gracefully (count < k under-fills the packed
        buffer; count >> k defers overflow to the residual). Exactness of
        EF bookkeeping never depends on the threshold's quality.
    """
    del rng, sigma_scale  # registry-signature parity; see the EF form
    n = acc.shape[0]
    _require_capacity(n, k, density)
    with jax.named_scope("ef_select"):
        vals, idxs, count = fused_select_candidates(acc, state, density,
                                                    interpret)
    sent_idx, val = _select_candidates_topk(vals, idxs, k, n)
    comp, residual = finish_pack(acc, sent_idx, val.astype(acc.dtype))
    valid = sent_idx < n
    t_new = _controller_update(state, count, val, valid, k, gain)
    # cold bootstrap (t <= 0) masks ~everything: report what was actually
    # selected instead of nnz(acc), so the logged selection count
    # (observability parity, base.py) keeps its ~k scale on that one step
    nsel = jnp.where(state > 0, count, jnp.sum(valid.astype(jnp.int32)))
    return CompressResult(comp, residual, nsel), t_new


def gaussian_fused_compress_batched(
    x: jax.Array, k: int, state: jax.Array,
    rng: Optional[jax.Array] = None, *, density: float = 0.001,
    sigma_scale: Optional[float] = None, gain: float = 0.18,
    interpret: Optional[bool] = None,
) -> Tuple[CompressResult, jax.Array]:
    """gaussian_fused over ``[n_chunks, chunk]`` — the uniform-bucket form.

    The kernel path for uniform plans (VERDICT r4 item 3): ONE chunked
    ``pallas_call`` (grid leading axis = chunk, per-chunk thresholds in
    SMEM) replaces the per-chunk vmap that the sequential-grid kernel could
    not support, so ``DEFAULT_SELECTOR`` keeps its Pallas select+pack at
    exactly the scale where uniform plans become necessary. Branch-free
    like the flat form: every lane runs kernel -> top-k -> finish_pack
    every step; cold lanes bootstrap their threshold from their own k-th
    candidate magnitude (``_controller_update``) with no cross-lane
    coupling — a persistently-cold lane can never drag warm lanes into a
    recovery path, because no recovery path exists.
    """
    del rng, sigma_scale  # registry-signature parity; see the EF form
    n_chunks, chunk = x.shape
    _require_capacity(chunk, k, density)
    with jax.named_scope("ef_select"):
        vals, idxs, counts = fused_select_candidates_chunked(
            x, state, density, interpret)
    sent_idx, val = jax.vmap(
        lambda vc, ic: _select_candidates_topk(vc, ic, k, chunk))(vals, idxs)
    val = val.astype(x.dtype)
    comp, residual = jax.vmap(finish_pack)(x, sent_idx, val)
    valid = sent_idx < chunk
    t_new = _controller_update(state, counts, val, valid, k, gain)
    # per-lane cold-bootstrap count fix — see gaussian_fused_compress
    nsel = jnp.where(state > 0, counts,
                     jnp.sum(valid.astype(jnp.int32), axis=-1))
    return CompressResult(comp, residual, nsel), t_new


def gaussian_fused_ef_compress_batched(
    res2d: jax.Array, g2d: jax.Array, scale: jax.Array, k: int,
    state: jax.Array, rng: Optional[jax.Array] = None, *,
    density: float = 0.001, sigma_scale: Optional[float] = None,
    gain: float = 0.18, interpret: Optional[bool] = None,
) -> Tuple[CompressResult, jax.Array]:
    """gaussian_fused with the EF accumulate folded INTO the kernel pass —
    the single-pass form the throughput contract needs at 15-60M params.

    Same warm/cold controller, candidate contract, and EF bookkeeping as
    ``gaussian_fused_compress_batched``; the difference is purely in HBM
    traffic: the caller hands the carried residual and the raw (scaled-in-
    kernel) gradient as pre-padded ``[n_chunks, chunk_pad]`` views and the
    kernel performs ``acc = res + scale*g`` in the same pass that emits
    candidates. The returned ``CompressResult.residual`` IS the new padded
    EF buffer (acc with the k sent entries zeroed) — no pad stripping:
    the pad region carries zeros in, stays unselected (thresholds >= 0,
    strict ``>`` mask), and carries zeros out.

    ``sigma_scale`` is accepted for registry-signature parity and unused:
    the fused path never computes a Gaussian estimate (the cold bootstrap
    adopts the k-th candidate magnitude instead).
    """
    del rng, sigma_scale  # signature parity with the unfused batched form
    n_chunks, chunk_pad = res2d.shape
    if ef_padded_chunk(chunk_pad, k, density=density) != chunk_pad:
        # reaching this path with unpadded chunks means the caller's
        # build-time eligibility gate is broken — fail loud
        raise ValueError(
            f"fused EF path needs pre-padded block-aligned chunks with "
            f"k <= capacity: got chunk={chunk_pad}, k={k}, "
            f"density={density} (ef_padded_chunk -> "
            f"{ef_padded_chunk(chunk_pad, k, density=density)})")
    with jax.named_scope("ef_select"):
        acc, vals, idxs, counts = fused_ef_select_candidates_chunked(
            res2d, g2d, scale, state, density, interpret)
    sent_idx, val = jax.vmap(
        lambda vc, ic: _select_candidates_topk(vc, ic, k, chunk_pad)
    )(vals, idxs)
    val = val.astype(acc.dtype)
    comp, residual = jax.vmap(finish_pack)(acc, sent_idx, val)
    valid = sent_idx < chunk_pad
    t_new = _controller_update(state, counts, val, valid, k, gain)
    nsel = jnp.where(state > 0, counts,
                     jnp.sum(valid.astype(jnp.int32), axis=-1))
    return CompressResult(comp, residual, nsel), t_new


def pack_wire_words(idx2d: jax.Array, val2d: jax.Array) -> jax.Array:
    """Wire-pack tail of the fused select pass: chunk-local selections ->
    one u32 word per entry (u16 bucket-relative index | bf16 value bits,
    parallel/wire.py layout).

    The fused kernel's ``CompressResult`` already carries CHUNK-LOCAL
    ``[n_chunks, k]`` indices — exactly the bucket-relative form the wire
    format transmits — so the packed exchange buffer is produced straight
    from the select pass's output, before (and instead of) the global i32
    offset materialization the legacy path needs. Like the rest of the
    pack tail (``_select_candidates_topk`` -> ``finish_pack``) this is a
    k-sized XLA epilogue, not an n-sized kernel pass. The caller's
    eligibility gate guarantees the chunk span fits u16 (chunk <= 65536;
    valid indices are < the UNPADDED chunk, and sentinel slots were
    already mapped to index 0 with value 0 by ``finish_pack``).
    """
    # function-local import: ops <- compressors.registry <- parallel is the
    # package import order; importing parallel.wire at module scope here
    # would close the cycle during compressors/__init__
    from ..parallel.wire import encode_entries
    return encode_entries(idx2d, val2d)
