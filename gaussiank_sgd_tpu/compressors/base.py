"""Core types and shared machinery for gradient compressors.

Reference parity: ``compression.py`` in sb17v/GaussianK-SGD (SURVEY.md §2 C1).
The reference exposes per-tensor ``compress(tensor, name, sigma_scale, ratio)``
methods plus a class-level residual store for error feedback. Here every
compressor is a *pure function* from ``(accumulated_gradient, hyper, rng)`` to
``(CompressedGrad, residual)`` so the whole thing jits and shards; the residual
store lives in the train state as a sharded device array, never in Python
globals (SURVEY.md §2.3, §7 stage 1).

Design constraints imposed by XLA (static shapes):

* Every compressor returns *exactly* ``k`` packed ``(index, value)`` pairs,
  ``k = max(1, ceil(density * numel))`` computed statically at trace time.
* Selection that would return more than ``k`` entries is truncated
  deterministically by **lowest flat index first** (documented tie-breaking,
  SURVEY.md §7 hard part 1); fewer than ``k`` entries are padded with
  ``(index=0, value=0)`` pairs, which are no-ops under scatter-add
  decompression.
* The error-feedback residual zeroes exactly the entries that were actually
  packed (sent), so ``sent ⊎ residual == acc`` holds elementwise even under
  truncation/padding.
"""

from __future__ import annotations

import math
from typing import Callable, NamedTuple, Optional

import jax
from jax.typing import DTypeLike
import jax.numpy as jnp


class CompressedGrad(NamedTuple):
    """A fixed-size packed sparse gradient.

    ``indices`` are flat int32 indices into the (flattened) gradient buffer,
    ``values`` the corresponding entries. Padding slots hold ``(0, 0.0)``:
    harmless under scatter-*add* decompression.
    """

    indices: jax.Array  # int32[k]
    values: jax.Array   # float[k]

    @property
    def k(self) -> int:
        return self.indices.shape[-1]


class CompressResult(NamedTuple):
    compressed: CompressedGrad
    residual: jax.Array      # same shape as input acc; EF carry-over
    num_selected: jax.Array  # int32 scalar: how many entries crossed threshold
                             # (before truncation to k) — observability parity
                             # with the reference's logged selection counts.


# A compressor is (acc_flat, k, rng, hyper...) -> CompressResult.  Hyper-params
# are bound by the registry factory (see registry.py).
CompressorFn = Callable[..., CompressResult]


def k_for(numel: int, density: float) -> int:
    """Static top-k size for a tensor: max(1, ceil(density * numel)).

    Mirrors the reference's per-tensor k computation (SURVEY.md §2.3).
    """
    return max(1, int(math.ceil(float(density) * numel)))


# Above this many elements the pack switches from exact ``lax.top_k`` on the
# priority key to ``lax.approx_max_k`` (TPU PartialReduce, two-level
# block-then-merge select). Measured on v5e: exact top_k is ~0.7 ms at 270K
# but ~40 ms at 15M; approx_max_k is ~1.4-1.7 ms flat across that range.
_EXACT_PACK_MAX = 1 << 21


def pack_by_mask(acc: jax.Array, mask: jax.Array, k: int,
                 priority: str = "index") -> CompressResult:
    """Pack entries of ``acc`` where ``mask`` is True into exactly ``k`` slots.

    TPU-native compaction WITHOUT an n-sized scatter (XLA lowers a scatter
    with n updates to a serialized loop — measured ~93 ms on a 15M-element
    gradient): build a priority key that is positive exactly on selected
    entries, then take the top-k of the key — one fused sort-free select
    op. Anything not packed (truncation, or approx_max_k recall misses)
    stays in the error-feedback residual, so no gradient mass is ever lost
    (SURVEY.md §2.3 EF contract).

    ``priority``:

    * ``"index"`` (default) — key decreases in flat index; entries beyond
      ``k`` drop lowest-index-first (the documented deterministic
      truncation contract; f32 key note: above 2^24 elements nearby
      indices can collide to one key value — top_k then breaks ties by
      lowest index, so selection stays deterministic).
    * ``"magnitude"`` — key is the masked |acc| cast to bf16: overflow
      drops the SMALLEST-magnitude entries instead (algorithmically
      stronger — the residual keeps the least mass), and the key costs
      half the HBM traffic of the f32 index key. Measured on the 57M
      transformer this cuts the warm pack from ~10 ms to approxtopk16-
      class cost. Entries whose magnitude rounds to bf16 zero are not
      packed and stay in the residual.
    """
    sent_idx, val, num_selected = select_by_mask(acc, mask, k, priority)
    return CompressResult(*finish_pack(acc, sent_idx, val), num_selected)


def select_by_mask(acc: jax.Array, mask: jax.Array, k: int,
                   priority: str = "index",
                   ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The selection half of :func:`pack_by_mask`: ``(sent_idx [k], val
    [k], num_selected)`` with the out-of-range sentinel ``n`` marking
    invalid slots. Split out so stateful compressors can route ONLY these
    small arrays through a ``lax.cond`` and build the n-sized residual
    once outside — a big buffer returned from a cond branch costs a full
    copy at the cond boundary (measured ~1 HBM pass at 57M, r5)."""
    n = acc.shape[0]
    num_selected = jnp.sum(mask.astype(jnp.int32))
    if priority == "magnitude":
        key = jnp.where(mask, jnp.abs(acc), 0.0).astype(jnp.bfloat16)
    else:
        key = jnp.where(mask,
                        jnp.float32(n) - jnp.arange(n, dtype=jnp.float32),
                        0.0)
    if n <= _EXACT_PACK_MAX:
        kv, ki = jax.lax.top_k(key, k)
    else:
        kv, ki = jax.lax.approx_max_k(key, k, recall_target=0.95)
    valid = kv > 0                                  # selected (not key-0 pad)
    val = jnp.where(valid, acc[jnp.where(valid, ki, 0)],
                    jnp.zeros((), acc.dtype))
    sent_idx = jnp.where(valid, ki, n).astype(jnp.int32)
    return sent_idx, val, num_selected


def finish_pack(acc: jax.Array, sent_idx: jax.Array, val: jax.Array,
                ) -> tuple[CompressedGrad, jax.Array]:
    """(CompressedGrad, residual) from a sentinel-marked selection: zero
    exactly the sent entries (invalid slots scatter out-of-range and
    drop); packed indices map the sentinel back to 0."""
    n = acc.shape[0]
    with jax.named_scope("pack"):
        valid = sent_idx < n
        idx = jnp.where(valid, sent_idx, 0)
    with jax.named_scope("scatter"):
        residual = acc.at[sent_idx].set(0.0, mode="drop")
    return CompressedGrad(idx, val), residual


def pack_by_threshold(acc: jax.Array, threshold: jax.Array, k: int) -> CompressResult:
    """Select |acc| > threshold and pack into exactly k slots (see pack_by_mask)."""
    return pack_by_mask(acc, jnp.abs(acc) > threshold, k)


def decompress(compressed: CompressedGrad, numel: int,
               dtype: DTypeLike = jnp.float32) -> jax.Array:
    """Scatter a packed sparse gradient back to a dense flat buffer.

    Padding slots (index 0, value 0) add zero, so they are no-ops. When the
    same index appears from several workers the contributions *sum*, matching
    the reference's decompress-then-sum allgather semantics (SURVEY.md §3.1).
    """
    dense = jnp.zeros((numel,), dtype)
    return dense.at[compressed.indices].add(compressed.values.astype(dtype))


def bisect_threshold(abs_acc: jax.Array, k: int, t0: jax.Array,
                     num_iters: int = 10,
                     tol: float = 0.05) -> jax.Array:
    """Refine a selection threshold so that ``|{|x| > t}| ≈ k``.

    Starts from an analytic estimate ``t0`` (e.g. the Gaussian tail-CDF
    estimate) and runs a fixed number of bisection steps on ``[0, max|x|]`` —
    the jit-friendly equivalent of the reference's ≤10 multiplicative
    threshold-adjustment iterations (SURVEY.md §2.3 "GaussianK threshold
    selection"). Stops moving once the count is within ``tol·k`` of target.
    """
    hi0 = jnp.max(abs_acc)
    lo0 = jnp.zeros_like(hi0)
    t0 = jnp.clip(t0, lo0, hi0)
    k_arr = jnp.asarray(k, jnp.int32)
    # never accept a zero-selection threshold: floor((1-tol)*k) is 0 at k=1,
    # which would let small tensors (biases at low density) send nothing
    lo_tol = jnp.maximum(1, jnp.floor((1.0 - tol) * k)).astype(jnp.int32)
    hi_tol = jnp.ceil((1.0 + tol) * k).astype(jnp.int32)

    def body(_, carry):
        t, lo, hi = carry
        cnt = jnp.sum(abs_acc > t).astype(jnp.int32)
        within = (cnt >= lo_tol) & (cnt <= hi_tol)
        # count too high -> threshold too low -> move lo up; and vice versa.
        new_lo = jnp.where(cnt > k_arr, t, lo)
        new_hi = jnp.where(cnt > k_arr, hi, t)
        new_t = 0.5 * (new_lo + new_hi)
        t = jnp.where(within, t, new_t)
        lo = jnp.where(within, lo, new_lo)
        hi = jnp.where(within, hi, new_hi)
        return t, lo, hi

    t, _, _ = jax.lax.fori_loop(0, num_iters, body, (t0, lo0, hi0))
    return t
