"""Pallas TPU kernels for the hot compression op: threshold estimation.

Reference parity: the performance-critical core of ``GaussianCompressor``
(SURVEY.md §2.3, §7 stage 6). The XLA composite in compressors/gaussian.py
costs ~13 sequential passes over the gradient (mean, std, 10 bisection
count-passes, pack); at ResNet-50 scale the cost is HBM bandwidth, so the
win is collapsing the data-dependent search into a fixed, tiny number of
passes.

Design — 3 passes, <= ~35 VPU ops/element:

  1. ``fused_stats``: one pass -> (sum, sum_sq, abs_max). Gives mu/sigma
     (the Gaussian estimate, kept for parity + observability) and the search
     upper bound.
  2. ``multi_threshold_counts`` with 32 LOG-spaced candidates spanning
     [~0.05*sigma, abs_max]: one pass, each element compared against all 32
     candidates simultaneously (a [chunk, 32] broadcast-compare -> column
     sum; vector-unit friendly, no scatter, no sort).
  3. The same kernel again with 32 LINEAR candidates inside the bracketing
     interval from pass 2 -> threshold resolved to ~1/1000 of the magnitude
     range, i.e. selected-count error well inside the reference's 5%
     bisection tolerance (SURVEY.md §2.3).

The pack (cumsum + scatter of k entries) stays in XLA — it is one fused pass
and fusing a compaction into the kernel would serialize the VPU
(pallas_guide.md: avoid scalar loops).

``interpret=True`` keeps everything testable on the CPU mesh
(tests/conftest.py). A train step binds the mode from its mesh's platform;
``interpret=None`` (direct calls) means the process's default backend
(ops/pallas_pack.resolve_interpret).

Status note (measured r2, TPU v5e, ResNet-20/b1024/density 0.1%): this
3-pass estimator benches at 14.3 ms/step vs 12.6 ms for the XLA
mean/std+bisection composite and 11.9 ms for ``approxtopk`` — the pack
dominates at small model sizes, so cutting estimator passes does not pay
there. It is superseded as the fast path by ``gaussian_warm``
(compressors/gaussian.py): carrying the threshold across steps needs ZERO
search passes, strictly fewer than any in-step estimator can achieve. The
kernel stays as the in-step estimator for single-shot compression (no
state) and as the Pallas reference implementation (SURVEY.md §7 stage 6).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..compressors.base import CompressResult, pack_by_threshold
from .pallas_pack import resolve_interpret

_NCAND = 32           # candidate thresholds per counting pass
_CHUNK = 8 * 128 * 8  # 8192 elements per grid step


def _spec(block=None, index_map=None, smem=False):
    space = pltpu.SMEM if smem else pltpu.VMEM
    if block is None:
        return pl.BlockSpec(memory_space=space)
    return pl.BlockSpec(block, index_map, memory_space=space)


def _stats_kernel(x_ref, sum_ref, sumsq_ref, amax_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sum_ref[0, 0] = 0.0
        sumsq_ref[0, 0] = 0.0
        amax_ref[0, 0] = 0.0

    x = x_ref[:]
    sum_ref[0, 0] += jnp.sum(x)
    sumsq_ref[0, 0] += jnp.sum(x * x)
    amax_ref[0, 0] = jnp.maximum(amax_ref[0, 0], jnp.max(jnp.abs(x)))


def fused_stats(flat: jax.Array, interpret: Optional[bool] = None
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One pass: (sum, sum_of_squares, abs_max). Zero-padding is harmless."""
    interpret = resolve_interpret(interpret)
    n = flat.shape[0]
    pad = (-n) % _CHUNK
    x = jnp.pad(flat.astype(jnp.float32), (0, pad)).reshape(-1, 128)
    rows = _CHUNK // 128
    grid = (x.shape[0] // rows,)
    s, ss, amax = pl.pallas_call(
        _stats_kernel,
        name="select_stats",
        grid=grid,
        in_specs=[_spec((rows, 128), lambda i: (i, 0))],
        out_specs=(_spec(smem=True), _spec(smem=True), _spec(smem=True)),
        out_shape=(jax.ShapeDtypeStruct((1, 1), jnp.float32),) * 3,
        interpret=interpret,
    )(x)
    return s[0, 0], ss[0, 0], amax[0, 0]


def _count_kernel(x_ref, t_ref, counts_ref):
    # t_ref/counts_ref live in SMEM; the candidate loop is a static unroll of
    # NCAND vector compare+reduce ops over the VMEM block — Mosaic-friendly
    # (no shape casts; a [chunk,1]x[1,NCAND] broadcast-compare reshape is an
    # unsupported vector layout cast on TPU).
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        for j in range(_NCAND):
            counts_ref[0, j] = 0.0

    ax = jnp.abs(x_ref[:])                         # [rows, 128]
    for j in range(_NCAND):
        counts_ref[0, j] += jnp.sum(
            (ax > t_ref[0, j]).astype(jnp.float32))


def multi_threshold_counts(flat: jax.Array, thresholds: jax.Array,
                           interpret: Optional[bool] = None) -> jax.Array:
    """One pass: counts[j] = |{ |x| > thresholds[j] }| for NCAND candidates."""
    interpret = resolve_interpret(interpret)
    n = flat.shape[0]
    pad = (-n) % _CHUNK
    x = jnp.pad(flat.astype(jnp.float32), (0, pad)).reshape(-1, 128)
    rows = _CHUNK // 128
    grid = (x.shape[0] // rows,)
    t = thresholds.astype(jnp.float32).reshape(1, _NCAND)
    counts = pl.pallas_call(
        _count_kernel,
        name="select_counts",
        grid=grid,
        in_specs=[_spec((rows, 128), lambda i: (i, 0)),
                  _spec((1, _NCAND), lambda i: (0, 0), smem=True)],
        out_specs=_spec((1, _NCAND), lambda i: (0, 0), smem=True),
        out_shape=jax.ShapeDtypeStruct((1, _NCAND), jnp.float32),
        interpret=interpret,
    )(x, t)
    return counts[0]


def _bracket(thresholds: jax.Array, counts: jax.Array, k: int
             ) -> Tuple[jax.Array, jax.Array]:
    """Pick [lo, hi] candidate interval with count(lo) >= k >= count(hi).

    counts are non-increasing in the (ascending) thresholds; choose the last
    index with count >= k as lo and the next as hi.
    """
    k_f = jnp.float32(k)
    ge = counts >= k_f                       # prefix of ascending thresholds
    # index of last True (0 if none)
    idx = jnp.where(jnp.any(ge),
                    _NCAND - 1 - jnp.argmax(ge[::-1]), 0).astype(jnp.int32)
    lo = thresholds[idx]
    hi = thresholds[jnp.minimum(idx + 1, _NCAND - 1)]
    # degenerate cases: k above all counts -> [0, t0]; k below all -> [t_max, t_max]
    lo = jnp.where(jnp.any(ge), lo, 0.0)
    hi = jnp.where(jnp.any(ge), hi, thresholds[0])
    return lo, hi


def pallas_threshold_estimate(flat: jax.Array, k: int,
                              interpret: Optional[bool] = None) -> jax.Array:
    """Threshold t with |{|x| > t}| ~= k in 3 single-pass kernels."""
    s, ss, amax = fused_stats(flat, interpret=interpret)
    n = flat.shape[0]
    mu = s / n
    sigma = jnp.sqrt(jnp.maximum(ss / n - mu * mu, 1e-30))
    # pass 2: log-spaced candidates from deep inside the bulk to the max
    lo0 = jnp.maximum(0.05 * sigma, amax * 1e-7) + 1e-30
    hi0 = jnp.maximum(amax, lo0 * 2.0)
    log_cand = lo0 * jnp.exp(
        jnp.linspace(0.0, 1.0, _NCAND) * jnp.log(hi0 / lo0))
    c1 = multi_threshold_counts(flat, log_cand, interpret=interpret)
    lo, hi = _bracket(log_cand, c1, k)
    # pass 3: linear candidates inside the bracket
    lin_cand = lo + (hi - lo) * jnp.linspace(0.0, 1.0, _NCAND)
    c2 = multi_threshold_counts(flat, lin_cand, interpret=interpret)
    # choose the candidate whose count is nearest k (ties -> larger count)
    j = jnp.argmin(jnp.abs(c2 - jnp.float32(k)))
    return lin_cand[j]


def pallas_gaussian_compress(acc: jax.Array, k: int,
                             rng: Optional[jax.Array] = None,
                             *, interpret: Optional[bool] = None
                             ) -> CompressResult:
    """GaussianK-equivalent compressor with the Pallas multi-pass estimator.

    Drop-in for ``gaussiank_compress`` (same CompressResult contract,
    including exact EF residual bookkeeping via the shared pack).
    """
    t = pallas_threshold_estimate(acc, k, interpret=interpret)
    return pack_by_threshold(acc, t, k)
