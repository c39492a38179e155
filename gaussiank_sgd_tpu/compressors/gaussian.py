"""GaussianK: analytic Gaussian-tail threshold estimation + mask selection.

Reference parity: ``GaussianCompressor`` in ``compression.py``
(SURVEY.md §2 C1, §2.3 "GaussianK threshold selection"), the headline
contribution of the reference (Shi et al., arXiv:1911.08772): model the
error-feedback-accumulated gradient as N(mu, sigma^2), derive the selection
threshold from the inverse Gaussian tail CDF so that P(|x| > t) ~= density,
then refine with a bounded number of adjustment iterations. Cost is O(n)
reductions + a mask — no sort — which is exactly what the TPU VPU wants; the
fused single-pass version lives in ops/pallas_pack.py.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from scipy.special import ndtri  # host-side: threshold quantile is a
                                 # compile-time constant (density is static)

from .base import (CompressResult, bisect_threshold, finish_pack,
                   pack_by_mask, pack_by_threshold, select_by_mask)


def gaussian_threshold_estimate(acc: jax.Array, density: float,
                                sigma_scale: Optional[float] = None) -> jax.Array:
    """Initial threshold t0 = |mu| + s * sigma.

    ``s`` comes from the two-sided Gaussian tail quantile
    ``s = Phi^{-1}(1 - density/2)`` when ``sigma_scale`` is None (density is a
    static Python float, so this is a compile-time constant); the reference's
    CLI-exposed ``--sigma-scale`` knob (default 2.5, SURVEY.md §2.3) overrides
    it when given.
    """
    if sigma_scale is None:
        s = float(ndtri(1.0 - min(max(density, 1e-12), 0.5) / 2.0))
    else:
        s = float(sigma_scale)
    mu = jnp.mean(acc)
    sigma = jnp.std(acc)
    return jnp.abs(mu) + s * sigma


def gaussiank_compress(acc: jax.Array, k: int,
                       rng: Optional[jax.Array] = None,
                       *, density: float = 0.001,
                       sigma_scale: Optional[float] = None,
                       refine_iters: int = 10) -> CompressResult:
    """Gaussian-threshold selection packed to exactly k entries.

    1. t0 from the Gaussian tail estimate (O(n) mean/std reductions);
    2. <= ``refine_iters`` bisection refinements of t toward count ~= k
       (the reference's multiplicative adjustment loop, made jit-shaped);
    3. mask-select |acc| > t and pack the first k by index order
       (pack_by_threshold documents truncation/padding and keeps the EF
       residual exact).
    """
    abs_acc = jnp.abs(acc)
    t0 = gaussian_threshold_estimate(acc, density, sigma_scale)
    t = bisect_threshold(abs_acc, k, t0, num_iters=refine_iters)
    return pack_by_threshold(acc, t, k)


def gaussian_warm_compress(acc: jax.Array, k: int, state: jax.Array,
                           rng: Optional[jax.Array] = None,
                           *, density: float = 0.001,
                           sigma_scale: Optional[float] = None,
                           gain: float = 0.18,
                           ) -> tuple[CompressResult, jax.Array]:
    """GaussianK with a warm-started threshold — ZERO search passes.

    TPU-first observation (VERDICT r1, SURVEY.md §2.3 cost model): the
    error-feedback accumulator changes slowly between steps, so the
    selection threshold barely moves. Instead of re-deriving it every step
    (mean/std + ~10 bisection count passes, each a full HBM sweep), carry
    the threshold as compressor STATE across steps:

      * steady state: select with last step's threshold — the only
        full-array passes left are the mask itself and the pack, i.e. the
        same passes exact selection already needs;
      * controller: nudge ``t' = t * (count/k)^gain`` (clipped to [1/4, 4]
        per step) toward the fixed point count == k, using the selected
        count the pack already computed — a free scalar update. ``gain``
        is small (0.18) because the tail count is exponentially sensitive
        to the threshold: at t ~= 2.6 sigma, d(log count)/d(log t) ~= -7,
        so the loop gain is ~= 7*0.18 ~= 1.3 — critically damped tracking
        without oscillation;
      * cold start / recovery: when the carried threshold is unset (<= 0)
        or has drifted so far that count is outside [k/4, 4k], fall back
        to the full Gaussian estimate + bisection for that step.

    The state is per worker and per bucket (each worker's accumulator is
    its own), living in ``TrainState.comp_state`` — see
    parallel/trainstep.py. EF bookkeeping is exact regardless of where the
    threshold came from (pack_by_threshold contract).
    """
    abs_acc = jnp.abs(acc)
    mask_prev = abs_acc > state          # ONE pass; reused by the hot branch
    count_prev = jnp.sum(mask_prev)
    usable = (state > 0) & (count_prev >= k // 4) & (count_prev <= 4 * k)

    def warm(_):
        # magnitude-priority selection: bf16 key (half the HBM traffic of
        # the f32 index key) and overflow drops the SMALLEST entries — see
        # pack_by_mask. The cold path keeps index priority so it stays
        # bit-identical to the stateless gaussian reference path.
        si, v, ns = select_by_mask(acc, mask_prev, k, priority="magnitude")
        return si, v, ns, state

    def cold(_):
        t0 = gaussian_threshold_estimate(acc, density, sigma_scale)
        t = bisect_threshold(abs_acc, k, t0, num_iters=10)
        si, v, ns = select_by_mask(acc, abs_acc > t, k)
        return si, v, ns, t

    # only the k-sized selection goes through the cond; the n-sized
    # residual is built ONCE outside (a big buffer returned from a cond
    # branch pays a full copy at the boundary — measured ~1 HBM pass at
    # 57M, r5)
    sent_idx, val, nsel, t = jax.lax.cond(usable, warm, cold, operand=None)
    comp, residual = finish_pack(acc, sent_idx, val)
    result = CompressResult(comp, residual, nsel)
    ratio = (nsel.astype(jnp.float32) + 1.0) / float(k + 1)
    t_new = t * jnp.clip(ratio ** gain, 0.25, 4.0)
    return result, t_new


def gaussian_warm_compress_batched(x: jax.Array, k: int, state: jax.Array,
                                   rng: Optional[jax.Array] = None,
                                   *, density: float = 0.001,
                                   sigma_scale: Optional[float] = None,
                                   gain: float = 0.18,
                                   ) -> tuple[CompressResult, jax.Array]:
    """gaussian_warm over ``[n_chunks, chunk]`` with PER-LANE cold recovery
    behind one scalar cond.

    Why this exists (ADVICE r2, medium): vmapping :func:`gaussian_warm_compress`
    lowers its per-lane ``lax.cond`` to ``lax.select``, which executes BOTH
    branches — the cold Gaussian estimate + 10-pass bisection would run every
    step for every chunk, silently destroying the zero-search-pass property
    exactly in the scalable ``bucket_policy='uniform'`` configuration.

    Recovery structure (reworked per ADVICE r3: the r2 version replayed the
    cold path on ALL lanes whenever ANY lane left the count band, so one
    persistently-cold chunk — e.g. a near-empty gradient — forced the
    10-pass bisection every step for the whole batch and reset healthy
    lanes' thresholds):

      * steady state (every lane usable): the program is ONLY the vmapped
        mask + magnitude pack — zero search passes;
      * recovery (scalar ``any(~usable)`` cond): the estimate+bisection runs
        vmapped, but each lane adopts the fresh threshold ONLY if it was
        unusable — warm lanes keep their carried thresholds and their
        controller trajectory. A lane that stays outside the band pays the
        bisection again next step, but no longer drags the others with it.

    Both branches end in the shared magnitude-priority pack (the warm one
    reusing the count pass's mask, the recovery one re-masking with its
    per-lane ``t_eff``), so EF bookkeeping is exact everywhere.
    """
    abs_x = jnp.abs(x)
    mask_prev = abs_x > state[:, None]           # ONE pass over the buffer
    count_prev = jnp.sum(mask_prev, axis=1)
    usable = (state > 0) & (count_prev >= k // 4) & (count_prev <= 4 * k)

    def warm(_):
        # steady state: select with the mask the count pass already built —
        # no second full-buffer compare (code-review r4)
        si, v, ns = jax.vmap(lambda xc, mc: select_by_mask(
            xc, mc, k, priority="magnitude"))(x, mask_prev)
        return si, v, ns, state

    def recover(_):
        def one(xc, ac):
            t0 = gaussian_threshold_estimate(xc, density, sigma_scale)
            return bisect_threshold(ac, k, t0, num_iters=10)

        t_fresh = jax.vmap(one)(x, abs_x)
        t_eff = jnp.where(usable, state, t_fresh)
        si, v, ns = jax.vmap(lambda xc, ac, tc: select_by_mask(
            xc, ac > tc, k, priority="magnitude"))(x, abs_x, t_eff)
        return si, v, ns, t_eff

    # k-sized selection through the cond; [n_chunks, chunk] residual built
    # once outside (see gaussian_warm_compress — cond-boundary copy)
    sent_idx, val, nsel, t_eff = jax.lax.cond(jnp.all(usable), warm,
                                              recover, operand=None)
    comp, residual = jax.vmap(finish_pack)(x, sent_idx, val)
    result = CompressResult(comp, residual, nsel)
    ratio = (nsel.astype(jnp.float32) + 1.0) / float(k + 1)
    t_new = t_eff * jnp.clip(ratio ** gain, 0.25, 4.0)
    return result, t_new
