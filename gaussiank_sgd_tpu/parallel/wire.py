"""Compact 32-bit wire format for the sparse exchange (ISSUE 5).

The sparse exchange used to move each selected entry as an (int32 global
index, float32 value) pair — 64 bits per entry, and after PR 4 fused the
EF+select compute on-device, those 64 bits dominate the remaining gap to
the >=0.90 sparse:dense contract (last driver chip record, 2026-07-31:
vgg16 at 0.8115; ROADMAP.md keeps the figures). This module
halves the payload without changing the algorithm, combining the two
classic observations from the reference lineage: sparse comms volume is
the scaling bottleneck (gTop-k, Shi et al.), and low-precision gradient
payloads preserve convergence when error feedback absorbs the rounding
(QSGD-style value quantization).

Wire word (one ``uint32`` per entry)::

      31 ............. 16 15 .............. 0
     +-------------------+------------------+
     |  rel index (u16)  |  value (bf16)    |
     +-------------------+------------------+

* ``rel`` is the entry's index RELATIVE to its bucket's first element
  (``global_idx = bucket_id * chunk + rel``), so 16 bits suffice whenever
  every bucket spans <= 65536 elements.
* the value is bfloat16 — round-to-nearest of the f32 value, <= 1 ulp
  (2^-8 relative) error, absorbed back into the f32 EF residual on-device
  by the caller (parallel/trainstep.py), so no error accumulates.

Bucket ids are NEVER transmitted; the two exchange layouts reconstruct
them structurally:

* **grouped** (allgather): the packed buffer is bucket-major with a fixed
  number of slots per bucket (the compressor's ``out_k``), so an entry's
  bucket is ``position // slots`` — free arithmetic on the receiver.
* **sorted + counts** (gtopk butterfly): after merge rounds the entries
  are no longer grouped, so each round sends the entries sorted by global
  index plus a tiny ``int32[n_buckets]`` per-bucket count vector; the
  receiver recovers buckets via ``searchsorted(cumsum(counts), position)``.

Eligibility is a BUILD-TIME gate (``plan_wire_format``): a uniform bucket
plan whose chunk spans <= 65536 elements, with f32 gradients. Ineligible
builds keep the fp32+i32 format bit-identically (``WIRE_LEGACY``) — the
packed format is an overlay on the exchange, never a change to selection
or EF semantics.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.typing import DTypeLike

from ..compressors.base import CompressedGrad
from .bucketing import BucketPlan

#: name of the packed format: u16 bucket-relative index + bf16 value
WIRE_PACKED = "u16bf16"
#: name of the legacy format: i32 global index + f32 value (pre-ISSUE-5)
WIRE_LEGACY = "i32f32"

#: largest bucket span a u16 relative index can address (rel <= 65535,
#: so a bucket of exactly 2^16 elements still fits)
MAX_BUCKET_SPAN = 1 << 16


class WireFormat(NamedTuple):
    """Trace-time description of an ACTIVE packed wire format.

    Existence of a ``WireFormat`` means the build passed the eligibility
    gate; ``None`` everywhere means the legacy fp32+i32 path. ``chunk`` is
    the uniform bucket span (the stride between consecutive buckets'
    first elements in the global flat space)."""

    name: str               # WIRE_PACKED
    chunk: int              # uniform bucket span (elements)
    n_buckets: int          # buckets in the plan (incl. a trailing pad chunk)
    bytes_per_entry: int = 4


def plan_wire_format(plan: BucketPlan,
                     grad_dtype: DTypeLike) -> Optional[WireFormat]:
    """Build-time eligibility gate. Returns the active ``WireFormat`` or
    ``None`` (legacy fp32+i32, bit-identical to the pre-wire program).

    Eligible iff ALL hold:

    * the plan is uniform (every bucket the same (size, k)) and tiles the
      flat space contiguously at stride ``chunk`` — both bucket policies
      produce contiguous tilings, so this is a defensive re-check;
    * ``chunk <= 65536`` so every bucket-relative index fits u16;
    * ``grad_dtype == float32`` — the format quantizes f32 values to
      bf16 and feeds the rounding error back into an f32 residual; a
      bf16 gradient path has no error to absorb it into (and its values
      are already 16-bit, so packing would not halve anything).
    """
    if jnp.dtype(grad_dtype) != jnp.float32:
        return None
    if not plan.uniform:
        return None
    chunk = plan.buckets[0].size
    if chunk > MAX_BUCKET_SPAN:
        return None
    for i, b in enumerate(plan.buckets):
        if b.offset != i * chunk or b.size != chunk:
            return None
    return WireFormat(WIRE_PACKED, chunk, len(plan.buckets))


def quantize_values(values: jax.Array) -> jax.Array:
    """f32 -> bf16 (round-to-nearest-even), the wire's value precision."""
    return values.astype(jnp.bfloat16)


def dequantize_values(q: jax.Array) -> jax.Array:
    """bf16 -> f32 (exact: bf16 is a prefix of f32)."""
    return q.astype(jnp.float32)


def bf16_roundtrip(values: jax.Array) -> jax.Array:
    """The f32 values as the receiver will see them (quantize + widen)."""
    return dequantize_values(quantize_values(values))


def encode_entries(rel_idx: jax.Array, values: jax.Array) -> jax.Array:
    """Pack (bucket-relative index, f32 value) into one u32 word each.

    ``rel_idx`` must already be bucket-relative and < 2^16 (the caller's
    layout codec guarantees it); any shape is accepted — the word layout
    is elementwise."""
    vbits = lax.bitcast_convert_type(
        values.astype(jnp.bfloat16), jnp.uint16).astype(jnp.uint32)
    return (rel_idx.astype(jnp.uint32) << 16) | vbits


def decode_entries(words: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Unpack u32 words -> (bucket-relative i32 indices, f32 values)."""
    rel = (words >> 16).astype(jnp.int32)
    vbits = (words & jnp.uint32(0xFFFF)).astype(jnp.uint16)
    return rel, lax.bitcast_convert_type(vbits, jnp.bfloat16).astype(
        jnp.float32)


def encode_grouped(comp: CompressedGrad, wf: WireFormat) -> jax.Array:
    """Encode a bucket-major packed gradient for the allgather exchange.

    ``comp`` is the global-index form from ``compress_buckets`` /
    ``_compress_phase``: ``slots`` entries per bucket, bucket-major, so an
    entry's bucket id is its position divided by ``slots`` — no bucket ids
    need to travel. Padding entries carry their bucket's base index with
    value 0 and decode to a scatter-add no-op."""
    k_packed = comp.indices.shape[0]
    if k_packed % wf.n_buckets:
        raise ValueError(
            f"packed length {k_packed} is not bucket-major over "
            f"{wf.n_buckets} buckets")
    slots = k_packed // wf.n_buckets
    bucket = jnp.arange(k_packed, dtype=jnp.int32) // slots
    rel = comp.indices - bucket * wf.chunk
    return encode_entries(rel, comp.values)


def decode_grouped(words: jax.Array, wf: WireFormat,
                   k_packed_local: int) -> CompressedGrad:
    """Decode a (possibly all-gathered) grouped buffer back to global form.

    ``words`` is ``[W * k_packed_local]`` for W >= 1 tiled worker payloads
    (W == 1 for a local round trip). Bucket ids are reconstructed from the
    position WITHIN each worker's payload — no i32 index buffer ever moves
    over the wire or is gathered."""
    if words.shape[0] % k_packed_local:
        raise ValueError(
            f"gathered length {words.shape[0]} is not a whole number of "
            f"{k_packed_local}-entry worker payloads")
    slots = k_packed_local // wf.n_buckets
    pos = jnp.arange(words.shape[0], dtype=jnp.int32) % k_packed_local
    bucket = pos // slots
    rel, vals = decode_entries(words)
    return CompressedGrad(bucket * wf.chunk + rel, vals)


def encode_sorted(idx: jax.Array, val: jax.Array,
                  wf: WireFormat) -> Tuple[jax.Array, jax.Array]:
    """Encode one gtopk butterfly round's payload: entries sorted by
    global index (so same-bucket entries are contiguous) plus the
    ``int32[n_buckets]`` per-bucket count vector that replaces per-entry
    bucket ids. Needed because butterfly merges destroy the bucket-major
    grouping the allgather layout relies on."""
    order = jnp.argsort(idx)
    s_idx = idx[order]
    s_val = val[order]
    bucket = s_idx // wf.chunk
    counts = jnp.zeros((wf.n_buckets,), jnp.int32).at[bucket].add(1)
    return encode_entries(s_idx - bucket * wf.chunk, s_val), counts


def decode_sorted(words: jax.Array, counts: jax.Array,
                  wf: WireFormat) -> Tuple[jax.Array, jax.Array]:
    """Decode a sorted+counts gtopk payload back to (global i32, f32).

    Position j belongs to bucket b iff ``cumsum(counts)[b-1] <= j <
    cumsum(counts)[b]`` — one k-sized searchsorted, no index buffer on
    the wire."""
    ends = jnp.cumsum(counts)
    pos = jnp.arange(words.shape[0], dtype=jnp.int32)
    bucket = jnp.searchsorted(ends, pos, side="right").astype(jnp.int32)
    rel, vals = decode_entries(words)
    return bucket * wf.chunk + rel, vals
