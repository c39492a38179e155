"""The fused data-parallel train step — compute + compression + collectives.

Reference parity: this module replaces the reference's entire L2 layer
(``hv_distributed_optimizer.py`` + ``distributed_optimizer.py`` +
``allreducer.py`` — SURVEY.md §2 C2/C3/C4 and §3.1/§3.3): backward hooks,
fusion buffers, background comm threads, queues, events, and handles. On
TPU+XLA none of that machinery survives (SURVEY.md §7 design stance): ONE
jit-compiled SPMD program owns forward, backward, error-feedback accumulation,
per-bucket compression, the sparse all-gather exchange, decompress-sum, and
the inner optimizer update; XLA schedules and overlaps compute with ICI/DCN
collectives.

The algorithmic contract implemented here is SURVEY.md §2.3 exactly:

    acc      = residual + scale * g_local        (scale = lr(step) if lr is
                                                  folded before selection,
                                                  else 1)
    (idx, v) = select(acc, k)  per bucket        (compressor from C1)
    residual'= acc - sent                        (error feedback)
    G        = scatter_sum(all_gather(idx, v)) / P
    params  '= inner_optimizer(params, G)        (SGD/momentum/Nesterov/wd)

plus the dense warm-up path ``G = psum(g_local)/P`` (SURVEY.md §2.3 "Warm-up
dense allreduce") as a *separate jitted function*, so the hot sparse program
carries no warm-up branching.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax, shard_map
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.typing import DTypeLike

from ..compressors.base import CompressedGrad, decompress
from ..compressors.registry import CompressorSpec
from ..ops.pallas_pack import pack_wire_words
from . import wire as wire_mod
from .bucketing import BucketPlan
from .flat_opt import FlatSGDM


class TrainState(NamedTuple):
    """Training state. Everything is replicated across dp EXCEPT
    ``ef_residual``, which is genuinely per-worker (each worker's un-sent
    gradient mass from *its own* batch shards) and therefore lives as a
    flat ``[num_devices * total_numel]`` array sharded over the dp axes
    (contiguous per-worker slices) — so a checkpoint/restore or reshard
    preserves every worker's residual, not just worker 0's (SURVEY.md
    §2.3, §3.5: the reference likely drops EF state from checkpoints; we
    keep it, correctly sharded).
    """

    step: jax.Array          # int32 scalar (replicated)
    params: Any              # trainable pytree (replicated)
    model_state: Any         # non-trainable collections, e.g. BatchNorm
                             # running stats (replicated; dp-meaned each step)
    opt_state: optax.OptState  # (replicated)
    ef_residual: jax.Array   # float32[num_devices * total_numel], sharded
                             # over dp on dim 0 — worker p owns the
                             # contiguous [p*N, (p+1)*N) slice. FLAT on
                             # purpose: a [P, N] array's per-device [1, N]
                             # shard gets a degenerate (1,128)-tiled layout
                             # and XLA inserts full-buffer relayout copies
                             # converting to/from the flat math view every
                             # sparse step (measured r4: part of a
                             # 2.4-4.2 ms EF floor); the 1-D form keeps one
                             # linear T(1024) layout end to end.
                             # Checkpoints still store [P, N]
                             # (training/checkpoint.py reshapes at the
                             # edges), so the on-disk format is unchanged.
                             # On the fused EF+select path the per-worker
                             # row is BLOCK-PADDED (DPTrainStep.ef_numel >=
                             # total_numel; pad provably stays zero) and
                             # the checkpoint edges strip/re-add the pad —
                             # on disk it is always [P, total_numel].
    rng: jax.Array           # PRNG key (replicated)
    carry: Any = ()          # recurrent hidden state carried across steps
                             # (the reference's bptt "repackaging",
                             # SURVEY.md §3.2). Leaves are [batch, ...] and
                             # batch-dim sharded over dp — each worker owns
                             # the carry for its own batch rows. () for
                             # non-recurrent models.
    comp_state: Any = ()     # stateful-compressor carry (warm-started
                             # thresholds): float32[num_devices, n_buckets]
                             # sharded over dp — per worker AND per bucket,
                             # like ef_residual. () for stateless
                             # compressors.


class StepMetrics(NamedTuple):
    loss: jax.Array           # mean over global batch
    aux: Any                  # loss_fn auxiliary output (averaged over dp)
    grad_norm: jax.Array      # dp-mean of per-worker flat-grad L2 norms
    num_selected: jax.Array   # dp-mean of entries crossing threshold (float,
                              # pre-truncation) — the reference's logged
                              # selection-count observability
    bytes_sent: jax.Array     # float32: per-worker payload of this step's
                              # exchange, in bytes. The count is trace-time
                              # static; it is carried as f32 because int64 is
                              # unavailable with x64 disabled and int32 wraps
                              # negative past a ~500M-param dense payload
                              # (VERDICT r3 weak #5) — exact below 16 MB,
                              # <1e-7 relative above
    skipped: jax.Array        # float32 0/1: the in-step non-finite guard
                              # turned this step into a no-op (params,
                              # opt_state, ef_residual, carry, comp_state
                              # all unchanged); step still advances
    nonfinite: jax.Array      # float32: global count of non-finite grad
                              # entries this step (+1 if the loss itself is
                              # non-finite); 0 on clean steps and when the
                              # guard is disabled
    # --- on-device telemetry accounting (docs/OBSERVABILITY.md): computed
    # inside the jitted step (psum'd alongside the existing metrics, zero
    # host sync) and drained with the rest of the metrics at log time ---
    achieved_density: jax.Array  # float32: dp-mean selected entries /
                              # total params (pre-truncation, like
                              # num_selected); 1.0 on the dense path
    ef_norm: jax.Array        # float32: global L2 norm of the COMMITTED
                              # error-feedback residual (all workers'
                              # shards; reflects the post-guard state, so
                              # a skipped step reports the old residual)
    sel_per_bucket: jax.Array  # float32[n_buckets]: dp-mean per-bucket
                              # selection counts — the per-bucket comms
                              # breakdown (dense path: bucket sizes)
    overlapped_bytes_sent: jax.Array  # float32: the subset of bytes_sent
                              # issued INSIDE the bucket-pipelined scan
                              # body, where XLA can latency-hide the
                              # collective behind the next chunk's
                              # compress (docs/ARCHITECTURE.md, the
                              # pipeline). 0 on the sequential program and
                              # the dense path. Trace-time static, f32
                              # for the same wrap-safety as bytes_sent.
    # --- span-source geometry (telemetry/tracing.py): trace-time-static
    # schedule shape, so the offline trace reconstruction can draw the
    # per-chunk/per-round comm spans without any new host sync ---
    pipeline_chunks: jax.Array  # float32: scan chunks the pipelined
                              # schedule ran (== n_buckets); 0 on the
                              # sequential program and the dense path
    comm_rounds: jax.Array    # float32: collective rounds per step —
                              # log2(P) on the gtopk butterfly, 1 for the
                              # one-shot allgather and the dense psum


# loss_fn(params, model_state, batch, rng)
#   -> (scalar loss, (new_model_state, aux pytree))
# ``model_state`` carries non-trainable collections (BatchNorm running stats);
# pure-param models pass/return an empty dict.
#
# Recurrent variant (``recurrent=True``):
# loss_fn(params, model_state, batch, rng, carry)
#   -> (scalar loss, (new_model_state, aux pytree, new_carry))
# ``carry`` is the hidden state from the previous bptt window; the loss fn
# consumes it as a constant (no gradient flows into past windows — the
# reference's *detaching* "repackage", SURVEY.md §3.2) and returns the final
# carry for the next window.
LossFn = Callable[..., Tuple[jax.Array, Any]]


def _microbatch_grads(loss_fn: LossFn, params: Any, model_state: Any,
                      batch: Any, rng: jax.Array, num_microbatches: int,
                      carry: Any = (), recurrent: bool = False):
    """Local grads, averaged over ``num_microbatches`` sequential microbatches.

    Reference parity: ``--nsteps-update`` gradient accumulation
    (SURVEY.md §2.2). The local batch's leading dim is split into
    ``num_microbatches`` equal chunks and scanned — constant memory in the
    accumulation factor. ``model_state`` threads through the microbatches
    sequentially (last microbatch's stats win, like sequential torch steps).
    ``carry`` splits along the batch dim like the batch itself (each
    microbatch advances its own rows' hidden state) and the per-chunk final
    carries reassemble into the full-batch carry.
    """
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def call(mstate, mb_i, rng_i, carry_i):
        if recurrent:
            (loss, (mstate, aux, c)), grads = grad_fn(params, mstate, mb_i,
                                                      rng_i, carry_i)
        else:
            (loss, (mstate, aux)), grads = grad_fn(params, mstate, mb_i,
                                                   rng_i)
            c = ()
        return loss, mstate, aux, c, grads

    if num_microbatches <= 1:
        return call(model_state, batch, rng, carry)

    for leaf in jax.tree_util.tree_leaves(batch):
        if leaf.shape[0] % num_microbatches:
            raise ValueError(
                f"per-worker batch dim {leaf.shape[0]} is not divisible by "
                f"nsteps_update={num_microbatches}; pick a batch size that "
                f"splits into equal microbatches (VERDICT r3 item 8)")

    def split(x):
        return x.reshape((num_microbatches, x.shape[0] // num_microbatches)
                         + x.shape[1:])

    mb = jax.tree.map(split, batch)
    mb_carry = jax.tree.map(split, carry)
    rngs = jax.random.split(rng, num_microbatches)

    def body(acc, inp):
        mb_i, rng_i, carry_i = inp
        c_loss, c_mstate, c_aux, c_grads = acc
        loss, mstate, aux, c, grads = call(c_mstate, mb_i, rng_i, carry_i)
        return ((c_loss + loss, mstate, jax.tree.map(jnp.add, c_aux, aux),
                 jax.tree.map(jnp.add, c_grads, grads)), c)

    first = lambda x: jax.tree.map(lambda v: v[0], x)
    rest = lambda x: jax.tree.map(lambda v: v[1:], x)
    loss0, mstate0, aux0, carry0, grads0 = call(
        model_state, first(mb), rngs[0], first(mb_carry))
    (loss, mstate, aux, grads), carry_rest = lax.scan(
        body, (loss0, mstate0, aux0, grads0),
        (rest(mb), rngs[1:], rest(mb_carry)))
    if recurrent:
        # reassemble [num_mb, B/num_mb, ...] chunk carries -> [B, ...]
        stacked = jax.tree.map(
            lambda c0, cr: jnp.concatenate([c0[None], cr]), carry0,
            carry_rest)
        new_carry = jax.tree.map(
            lambda x: x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:]),
            stacked)
    else:
        new_carry = ()
    inv = 1.0 / num_microbatches
    return (loss * inv, mstate, jax.tree.map(lambda x: x * inv, aux),
            new_carry, jax.tree.map(lambda x: x * inv, grads))


def _clip_by_global_norm(flat_g: jax.Array, clip: Optional[float]):
    """Pre-compression grad clipping (the reference's LSTM clip, SURVEY §3.2)."""
    if clip is None:
        return flat_g
    norm = jnp.linalg.norm(flat_g)
    scale = jnp.minimum(1.0, clip / (norm + 1e-12))
    return flat_g * scale


def _compressor_call(spec: CompressorSpec, chunk: jax.Array, k: int,
                     st: jax.Array, rg: jax.Array):
    """Uniform compressor-call convention: unused st/rg pass through so ONE
    code path serves all four (stateful x requires_rng) cases — shared by
    ``compress_buckets`` (vmapped and unrolled) and the bucket-pipelined
    step's per-chunk compress, which MUST route through the exact same
    machinery for bit-parity with the sequential program."""
    args = (chunk, k) + ((st,) if spec.stateful else ())
    r = spec.fn(*args, rg) if spec.requires_rng else spec.fn(*args)
    return r if spec.stateful else (r, st)


def compress_buckets(spec: CompressorSpec, plan: BucketPlan, acc: jax.Array,
                     rng: jax.Array, comp_state: Any = (),
                     ) -> Tuple[CompressedGrad, jax.Array, jax.Array, Any]:
    """Run the compressor over every bucket; concat packed pairs globally.

    Bucket-local indices are offset into the global flat space so the whole
    model exchanges as ONE (idx, val) pair of arrays — one collective per
    step no matter how many buckets (SURVEY.md §7 design stance). Returns
    (CompressedGrad over global flat indices, residual, num_selected,
    comp_state); ``num_selected`` is the PER-BUCKET int32 vector
    ``[n_buckets]`` of entries crossing each bucket's threshold
    (pre-truncation) — sum it for the scalar count.

    Uniform plans (every bucket same size+k, ``policy='uniform'``) take the
    vectorized path: one ``vmap`` of the compressor over a
    ``[n_chunks, chunk]`` view of the (zero-padded) flat buffer — compile
    time is O(1) in bucket count, vs one unrolled slice+compress body per
    bucket for boundary-respecting plans (VERDICT r1 weak #4). Zero padding
    never crosses a magnitude threshold; pad-region entries are stripped
    from the residual. Only the (possibly) trailing pad chunk's statistics
    see the zeros — same class of approximation as the reference's fused
    buckets mixing tensors.
    """
    call = functools.partial(_compressor_call, spec)

    if plan.uniform and len(plan.buckets) > 1:
        n_chunks = len(plan.buckets)
        chunk, k = plan.buckets[0].size, plan.buckets[0].k
        padded = n_chunks * chunk
        x = (jnp.pad(acc, (0, padded - acc.shape[0]))
             if padded > acc.shape[0] else acc).reshape(n_chunks, chunk)
        st = (comp_state if spec.stateful
              else jnp.zeros((n_chunks,), jnp.float32))
        # per-bucket RNG derivation matches the unrolled path's fold_in(rng, i)
        # exactly, so rng-consuming compressors (randomk/dgc) draw the same
        # indices under either bucket policy (ADVICE r2 low)
        rngs = jax.vmap(lambda i: jax.random.fold_in(rng, i))(
            jnp.arange(n_chunks, dtype=jnp.uint32))
        if spec.batched_fn is not None:
            r, st_new = spec.batched_fn(x, k, st, rngs)
        else:
            r, st_new = jax.vmap(lambda c, s, rg: call(c, k, s, rg))(
                x, st, rngs)
        offs = (jnp.arange(n_chunks, dtype=jnp.int32) * chunk)[:, None]
        comp = CompressedGrad((r.compressed.indices + offs).reshape(-1),
                              r.compressed.values.reshape(-1))
        residual = r.residual.reshape(-1)[:acc.shape[0]]
        return (comp, residual, r.num_selected.astype(jnp.int32).reshape(-1),
                st_new if spec.stateful else comp_state)

    idx_parts, val_parts, res_parts, nsel_parts = [], [], [], []
    st_parts = []
    for i, b in enumerate(plan.buckets):
        chunk = lax.dynamic_slice_in_dim(acc, b.offset, b.size)
        st_i = comp_state[i] if spec.stateful else jnp.float32(0)
        r, st_new = call(chunk, b.k, st_i, jax.random.fold_in(rng, i))
        idx_parts.append(r.compressed.indices + b.offset)
        val_parts.append(r.compressed.values)
        res_parts.append(r.residual)
        st_parts.append(st_new)
        nsel_parts.append(r.num_selected.astype(jnp.int32))
    comp = CompressedGrad(jnp.concatenate(idx_parts),
                          jnp.concatenate(val_parts))
    return (comp, jnp.concatenate(res_parts), jnp.stack(nsel_parts),
            jnp.stack(st_parts) if spec.stateful else comp_state)


class DPTrainStep(NamedTuple):
    """The compiled-step bundle the trainer drives.

    ``sparse_step`` / ``dense_step`` are jitted ``(state, batch) ->
    (state, StepMetrics)`` over the mesh; the trainer picks dense during
    warm-up (SURVEY.md §2.3) in plain Python — no traced epoch branching
    (SURVEY.md §7 stage 3).
    """

    sparse_step: Callable[[TrainState, Any], Tuple[TrainState, StepMetrics]]
    dense_step: Callable[[TrainState, Any], Tuple[TrainState, StepMetrics]]
    # (params, rng, model_state=None) -> TrainState
    init_state: Callable[..., TrainState]
    plan: BucketPlan
    mesh: Mesh
    # Per-worker EF-residual row size: plan.total_numel on the unfused
    # path, the block-aligned padded size when the fused EF+select kernel
    # owns the accumulate (ops/pallas_pack.py padded-EF contract). The
    # checkpoint edges (training/checkpoint.py) strip/re-add the pad so
    # the on-disk [P, N] format never changes.
    ef_numel: int = 0
    # Wire format of this build's sparse exchange (parallel/wire.py):
    # "u16bf16" when the packed format passed the eligibility gate,
    # "i32f32" otherwise (legacy, bit-identical to the pre-wire program).
    # Telemetry reports it next to every bytes_sent claim.
    wire_format: str = wire_mod.WIRE_LEGACY
    # "pipelined" when this build's sparse step runs the bucket-pipelined
    # schedule (per-chunk EF+select with the collective for chunk i issued
    # while chunk i+1 compresses — the double-buffered lax.scan), "off"
    # when it runs the historical sequential program (--overlap off or an
    # ineligible plan). Telemetry reports it next to every timing.
    overlap: str = "off"
    # How this build's Pallas kernels execute: "mosaic" (compiled for the
    # TPU), "interpret" (the Pallas interpreter, CPU meshes) or "none" (the
    # selector has no kernel). Decided ONCE here from the platform of the
    # mesh's devices — never from the process's default backend — so a
    # step built for a TPU mesh cannot carry an interpreted kernel.
    kernel_mode: str = "none"


def build_dp_train_step(
    loss_fn: LossFn,
    optimizer: Optional[optax.GradientTransformation],
    spec: CompressorSpec,
    plan: BucketPlan,
    mesh: Mesh,
    *,
    num_microbatches: int = 1,
    clip_norm: Optional[float] = None,
    fold_lr: Optional[Callable[[jax.Array], jax.Array]] = None,
    grad_dtype: DTypeLike = jnp.float32,
    exchange: str = "allgather",
    recurrent: bool = False,
    sp_axis: Optional[str] = None,
    flat_opt: Optional[FlatSGDM] = None,
    guard_nonfinite: bool = True,
    decorrelate_comp_rng: bool = False,
    wire: str = "auto",
    overlap: str = "auto",
) -> DPTrainStep:
    """Build the data-parallel train step over ``mesh``.

    ``fold_lr``: optional schedule ``step -> lr``. When given, the EF
    accumulator carries lr-scaled gradients (``acc = residual + lr*g``) and
    ``optimizer`` must be built with unit learning rate — this is the
    reference's fold-lr-before-selection variant (SURVEY.md §2.3 note). When
    None (default), EF runs on raw gradients and ``optimizer`` owns the lr —
    equivalent up to schedule, and friendlier to arbitrary optax chains.

    The mesh may be 1-D ``('dp',)`` or hierarchical ``('dcn_dp','ici_dp')``;
    with a hierarchical mesh the sparse all-gather stays on the (fast) last
    axis and only an already-dense partial crosses the first axis
    (SURVEY.md §7 hard part 3).

    ``exchange``: ``'allgather'`` (the reference's C2 path / north-star) or
    ``'gtopk'`` (the reference's C3 gTop-k tree allreduce, rebuilt as a
    ppermute butterfly — parallel/gtopk.py; 1-D power-of-2 meshes only).

    ``recurrent``: the loss fn follows the carry-threading protocol (see
    LossFn) and ``TrainState.carry`` holds batch-dim-sharded hidden state
    that persists across steps — the reference's bptt "repackaging"
    (SURVEY.md §3.2). Pass the initial carry to ``init_state``.

    ``guard_nonfinite``: fuse a non-finite anomaly guard into both step
    programs (training/resilience.py is the host half). The local grads'
    non-finite entry count is psum'd over the mesh so EVERY worker agrees,
    and the step is committed ONCE (``_commit``): ``ok`` is known before
    anything is written, one ``lax.cond`` holds everything that follows
    the exchange, its commit side updates parameters and optimizer state
    in place in the donated buffers and its keep side hands the old state
    back untouched — every worker takes the same side and neither holds a
    collective, so nothing diverges under shard_map; no host sync; the
    step counter AND the integer (counter) leaves of opt_state still
    advance so the LR schedule and data stream stay aligned on every
    optimizer path. Containment must be in-step because a NaN that
    reaches ``ef_residual`` is re-sent by error feedback on every later
    step. Cost: one reduction over the grads, which also yields the
    gradient norm (scope ``guard`` in a device trace), and no pass whose
    only work is ``where(ok, new, old)``. False: no count, no guard.

    ``sp_axis``: ring-attention sequence parallelism (long-context path).
    Must name the mesh's LAST axis; the batch's dim 0 then shards over the
    other (dp) axes and dim 1 (sequence) over ``sp_axis``, and the model
    inside ``loss_fn`` is expected to use the axis (e.g.
    ``TransformerLM(sp_axis=...)``'s K/V ring). Gradient math is unchanged:
    every (dp, sp) shard contributes partial grads and the existing
    gather-then-psum exchange sums over both axes.

    ``decorrelate_comp_rng``: fold the worker index into the compressor
    rng so rng-consuming compressors (randomk/randomkec/dgc) draw
    DIFFERENT indices on every worker, instead of the default shared-seed
    alignment (the reference's shared compressor seed). Deterministic
    compressors are unaffected. Exists for the convergence ablation in
    analysis/randomkec_decorrelated.py (VERDICT r5 weak #6: is randomkec's
    measured divergence intrinsic, or an artifact of index alignment?).

    ``wire``: ``'auto'`` (default) activates the compact u16+bf16 packed
    exchange format (parallel/wire.py — one u32 word per entry, half the
    fp32+i32 payload) when the build passes the eligibility gate: uniform
    bucket plan with chunk <= 65536 and f32 grads. On the allgather path
    the bf16 rounding error is fed back into the f32 EF residual
    on-device, so no quantization error accumulates; the gtopk butterfly
    merges in bf16-decoded space and re-packs per round (lossy exactly
    where the published gTop-k residual already is — see gtopk.py).
    ``'off'`` — or an ineligible build — keeps the legacy format with a
    program bit-identical to the pre-wire one. ``DPTrainStep.wire_format``
    reports which format the build actually uses.

    ``overlap``: ``'auto'`` (default) builds the BUCKET-PIPELINED sparse
    step when the plan is eligible: a uniform plan with >= 2 buckets (and,
    on gtopk, a gather axis of >= 2 workers). The pipelined program is a
    two-phase ``lax.scan`` over the uniform chunks — a prologue compresses
    chunk 0, then each scan iteration ISSUES the collective for chunk i's
    payload while compressing chunk i+1, with an epilogue collective for
    the last chunk — double-buffered so XLA can latency-hide each hop
    behind the next chunk's EF+select compute (the reference lineage's
    per-bucket comm/compute overlap, SURVEY.md §2 C2, rebuilt inside one
    SPMD program). Every per-chunk compress routes through the SAME
    batched compressor machinery as the sequential step (1-row batches)
    and the gathered chunks reassemble into the exact sequential buffer
    layout, so the pipelined step is bit-identical to the sequential one
    end to end (tests/test_overlap.py N-step parity). ``'off'`` — or an
    ineligible build — keeps the sequential program bit-identical to
    before this knob existed. ``DPTrainStep.overlap`` reports which
    schedule the build actually uses.
    """
    axes = tuple(mesh.axis_names)
    if sp_axis is not None:
        if sp_axis != axes[-1]:
            raise ValueError(
                f"sp_axis {sp_axis!r} must be the mesh's last axis {axes!r}")
        if recurrent:
            raise ValueError(
                "recurrent carry + sequence parallelism is not supported "
                "(carry rows are batch rows)")
    if exchange == "gtopk":
        if len(axes) != 1:
            raise ValueError("gtopk exchange supports 1-D dp meshes only")
        if mesh.size & (mesh.size - 1) != 0:
            raise ValueError("gtopk exchange needs a power-of-2 dp width")
    elif exchange != "allgather":
        raise ValueError(f"unknown exchange {exchange!r}")
    gather_axis = axes[-1]          # ICI axis on hierarchical meshes
    outer_axes = axes[:-1]          # DCN axes (empty on 1-D meshes)
    if flat_opt is not None:
        # the flat sparse-aware update needs the pairs to be the ONLY
        # gradient carrier: DCN outer axes psum a dense partial and
        # fold_lr rescales the accumulator — both take the optax path.
        # ValueError, not assert: silently-wrong training under -O
        # (repo convention, code-review r4/r5)
        if outer_axes or fold_lr is not None:
            raise ValueError(
                "flat_opt supports 1-D meshes without fold_lr; use the "
                "optax path otherwise")
        if optimizer is not None:
            raise ValueError(
                "pass optimizer=None with flat_opt — one optimizer "
                "config, no silent shadowing")
    n_total = plan.total_numel
    kernel_mode = "none"
    if spec.pallas:
        on_tpu = mesh.devices.flat[0].platform == "tpu"
        spec = spec.with_interpret(not on_tpu)
        kernel_mode = "mosaic" if on_tpu else "interpret"

    def _fused_ef_layout() -> Optional[Tuple[int, int, int]]:
        """(n_chunks, chunk, chunk_pad) when the fused EF+select kernel can
        own the EF accumulate for this (spec, plan, exchange) build, else
        None (unfused path, ef_numel == n_total).

        The fused path keeps the live EF buffer PRE-PADDED so the kernel's
        single HBM pass needs no jnp.pad copy (ops/pallas_pack.py). The
        geometry must keep every chunk's global offsets unchanged, so:

        * a single whole-model bucket pads purely at the tail (offset 0);
        * a uniform multi-chunk plan qualifies iff its chunk is already
          block-aligned (``ef_pad(chunk, k) == chunk`` — e.g. the 4M
          default of parallel/bucketing.py) — an in-chunk pad would shift
          every later chunk's indices;
        * gtopk needs the unpadded accumulator for ``global_residual``;
        * the kernel accumulates in f32, so grad_dtype must be f32 (the
          default) — a bf16 EF buffer would silently widen.
        """
        if (spec.fused_ef_fn is None or spec.ef_pad is None
                or exchange != "allgather"
                or jnp.dtype(grad_dtype) != jnp.float32):
            return None
        b0 = plan.buckets[0]
        cp = spec.ef_pad(b0.size, b0.k)
        if cp is None:
            return None
        if len(plan.buckets) == 1:
            return (1, b0.size, cp)
        if plan.uniform and cp == b0.size:
            return (len(plan.buckets), b0.size, cp)
        return None

    fused_ef = _fused_ef_layout()
    # per-worker EF-residual row size (padded on the fused path; the pad
    # region is provably zero forever — thresholds >= 0, strict > mask)
    ef_numel = fused_ef[0] * fused_ef[2] if fused_ef is not None else n_total

    if wire not in ("auto", "off"):
        raise ValueError(f"unknown wire {wire!r}; expected 'auto' or 'off'")
    # build-time wire gate (parallel/wire.py): None -> legacy fp32+i32
    # exchange, program bit-identical to the pre-wire build
    wire_fmt = (wire_mod.plan_wire_format(plan, grad_dtype)
                if wire == "auto" else None)

    if overlap not in ("auto", "off"):
        raise ValueError(
            f"unknown overlap {overlap!r}; expected 'auto' or 'off'")
    gather_size = mesh.shape[gather_axis]
    # build-time overlap gate: the pipelined scan needs the uniform-chunk
    # geometry (per-chunk payloads are fixed [k]-shaped and chunk-major
    # reassembly reconstructs the sequential buffer exactly); a single
    # bucket has nothing to overlap, and the gtopk round-1 ppermute needs
    # a partner. Ineligible builds keep the sequential program.
    pipelined = (overlap == "auto" and plan.uniform
                 and len(plan.buckets) >= 2
                 and (exchange != "gtopk" or gather_size >= 2))

    def _all_axes_size():
        p = 1
        for a in axes:
            p *= lax.psum(1, a)
        return p

    def _pmean(x):
        for a in axes:
            x = lax.pmean(x, a)
        return x

    def _linear_device_index():
        idx = jnp.int32(0)
        for a in axes:
            idx = idx * lax.axis_size(a) + lax.axis_index(a)
        return idx

    def _step_rngs(state: TrainState):
        """Two decorrelated streams from the state key (domain-separated).

        * data rng — additionally folded with the worker index, so dropout
          masks differ across dp shards (each shard sees different data);
        * compressor rng — identical on every shard, so randomk/dgc index
          draws align across workers, the SPMD analogue of the reference's
          shared compressor seed (SURVEY.md §2.3 RandomK). With
          ``decorrelate_comp_rng`` the worker index is folded in too, so
          every worker draws independent indices (ablation arm).
        """
        base = jax.random.fold_in(state.rng, state.step)
        data_rng = jax.random.fold_in(jax.random.fold_in(base, 0),
                                      _linear_device_index())
        comp_rng = jax.random.fold_in(base, 1)
        if decorrelate_comp_rng:
            comp_rng = jax.random.fold_in(comp_rng, _linear_device_index())
        return data_rng, comp_rng

    # trace-time constant: per-bucket element counts, the dense path's
    # "everything was sent" sel_per_bucket (telemetry accounting)
    bucket_sizes_f32 = tuple(float(b.size) for b in plan.buckets)

    def _ef_norm(residual: jax.Array) -> jax.Array:
        """Global L2 norm of the EF residual: local shard sum-of-squares
        psum'd over every mesh axis (each worker owns its own slice), then
        sqrt — replicated like the other metrics, no host sync."""
        with jax.named_scope("step_metrics"):
            ss = jnp.sum(jnp.square(residual.astype(jnp.float32)))
            for a in axes:
                ss = lax.psum(ss, a)
            return jnp.sqrt(ss)

    def _grad_count_and_norm(loss: jax.Array, flat_g: jax.Array):
        """``(cnt, grad_norm)`` from ONE pass over the flat gradient.

        ``cnt`` is the global non-finite count: the per-worker count of
        gradient entries psum'd over every mesh axis (all workers must
        agree — one worker's NaN pollutes the summed exchange for
        everyone), plus one for a non-finite loss (already dp-mean'd, so
        globally consistent); None when the guard is off. ``grad_norm`` is
        the dp-mean of the per-worker L2 norms. Count and sum of squares
        are the two outputs of one reduction, so the gradient is streamed
        once for both; the pass keeps the scope ``guard``."""
        if not guard_nonfinite:
            with jax.named_scope("step_metrics"):
                return None, _pmean(jnp.linalg.norm(flat_g))
        with jax.named_scope("guard"):
            g32 = flat_g.astype(jnp.float32)
            cnt, ss = lax.reduce(
                ((~jnp.isfinite(flat_g)).astype(jnp.int32), g32 * g32),
                (jnp.int32(0), jnp.float32(0)),
                lambda a, b: (a[0] + b[0], a[1] + b[1]), (0,))
            for a in axes:
                cnt = lax.psum(cnt, a)
            cnt = cnt + (~jnp.isfinite(loss)).astype(jnp.int32)
        with jax.named_scope("step_metrics"):
            return cnt, _pmean(jnp.sqrt(ss).astype(flat_g.dtype))

    def _commit(cnt: Optional[jax.Array], state: TrainState, mstate: Any,
                residual: jax.Array, new_carry: Any, comp_state: Any,
                update: Callable[[Any, Any], Tuple[Any, Any]]):
        """Commit the step ONCE: the guard decides first, then every
        buffer is written at most once. Returns ``(state', skipped,
        nonfinite)``, the last two for ``StepMetrics``.

        ``ok = cnt == 0`` (replicated: the psum'd count says the same on
        every worker) is known before anything is written. One ``lax.cond``
        holds everything that follows the exchange: its ``commit`` side
        runs ``update(params, opt_state) -> (params', opt_state')`` — the
        optimizer, in place in the donated buffers — and takes the new
        model state, residual, carry and compressor state; its ``keep``
        side hands the old ones back untouched, so a skipped step is
        bit-identical on every float leaf (a momentum entry of -0.0
        included: nothing is added to it) and neither side holds a
        collective. No old-or-new select over a vector of n survives, on
        this or on the optax path. The step counter and rng advance on
        both sides (a skipped step still moves the schedule and the data
        position), and so do the INTEGER leaves of opt_state: they are
        step/schedule counters (optax ScaleByScheduleState.count and kin)
        whose value must track state.step — holding them would make the
        optax-path LR schedule lag the global step by one per skip.
        Counter increments never touch the gradient, so a NaN cannot leak
        through them; ``keep`` takes them from the same ``update`` (whose
        float work XLA drops there) and every float leaf from the old
        state. ``cnt`` None (``guard_nonfinite=False``): no guard, the
        commit side alone."""
        def is_counter(x):
            return jnp.issubdtype(x.dtype, jnp.integer)

        def commit(old):
            params, opt_state = update(old.params, old.opt_state)
            return (params, mstate, opt_state, residual, new_carry,
                    comp_state)

        def keep(old):
            _, stepped = update(old.params, old.opt_state)
            opt_state = jax.tree.map(
                lambda new, o: new if is_counter(o) else o, stepped,
                old.opt_state)
            return (old.params, old.model_state, opt_state,
                    old.ef_residual, old.carry, old.comp_state)

        if cnt is None:
            out = commit(state)
            skipped = nonfinite = jnp.float32(0)
        else:
            with jax.named_scope("guard"):
                out = lax.cond(cnt == 0, commit, keep, state)
                skipped = (cnt > 0).astype(jnp.float32)
                nonfinite = cnt.astype(jnp.float32)
        params, model_state, opt_state, ef_residual, carry, comp_state = out
        return TrainState(state.step + 1, params, model_state, opt_state,
                          ef_residual, state.rng, carry, comp_state
                          ), skipped, nonfinite

    def _local_grads(state: TrainState, batch: Any, data_rng: jax.Array,
                     pad: int = 0):
        with jax.named_scope("fwd_bwd"):
            loss, mstate, aux, new_carry, grads = _microbatch_grads(
                loss_fn, state.params, state.model_state, batch, data_rng,
                num_microbatches, state.carry, recurrent)
        with jax.named_scope("flatten"):
            flat_g, unravel = _flat_grads(grads, pad)
            flat_g = _clip_by_global_norm(flat_g, clip_norm)
        # dp-mean of loss/aux/model-state for logging & replicated-stats
        # consistency (BatchNorm running stats are averaged across workers —
        # strictly better than the reference's per-GPU local stats). The
        # carry is NOT averaged: like the batch, it is per-worker data.
        def pmean_floats(x):
            return _pmean(x) if jnp.issubdtype(x.dtype, jnp.floating) else x
        with jax.named_scope("exchange"):
            mstate = jax.tree.map(pmean_floats, mstate)
        with jax.named_scope("step_metrics"):
            loss, aux = _pmean(loss), jax.tree.map(_pmean, aux)
        return loss, mstate, aux, new_carry, flat_g, unravel

    def _flat_grads(grads: Any, pad: int):
        if pad:
            # fused-EF path: build the flat grad directly at the padded
            # length (tree_leaves order == ravel_pytree order) so the
            # kernel's [n_chunks, chunk_pad] view is a free reshape; the
            # unravel closure still comes from ravel_pytree (its flat
            # output is unused and DCE'd). The zero tail leaves the global
            # norm — and therefore the clip — unchanged.
            leaves = jax.tree_util.tree_leaves(grads)
            flat_g = jnp.concatenate(
                [l.reshape(-1).astype(grad_dtype) for l in leaves]
                + [jnp.zeros((pad,), grad_dtype)])
            _, unravel = ravel_pytree(grads)
        else:
            flat_g, unravel = ravel_pytree(grads)
            flat_g = flat_g.astype(grad_dtype)
        return flat_g, unravel

    def _optax_update(dense_flat: jax.Array, unravel):
        """The optax path's ``update`` for ``_commit``."""
        def update(params, opt_state):
            with jax.named_scope("update"):
                updates, opt_state = optimizer.update(
                    unravel(dense_flat), opt_state, params)
                return optax.apply_updates(params, updates), opt_state
        return update

    def _compress_phase(state: TrainState, flat_g: jax.Array, scale,
                        comp_rng: jax.Array):
        """EF accumulate + per-bucket compression, shared by
        ``sparse_step_fn`` and the 'select' probe (so the logged phase
        decomposition times the REAL program, fused or not). Returns
        ``(comp global-offset pairs, residual, nsel, cstate, acc, words)``;
        ``acc`` is the materialized unfused accumulator (gtopk's
        ``global_residual`` needs it) or None on the fused path, where it
        only ever exists inside the kernel pass. ``words`` is the packed
        u32 wire buffer when the fused select pass emits it directly
        (active wire + fused path — ops/pallas_pack.pack_wire_words on the
        CHUNK-LOCAL selection, no global i32 index materialization), else
        None (the caller encodes from ``comp`` if the wire is active)."""
        if fused_ef is not None:
            n_chunks, chunk, chunk_pad = fused_ef
            # the local ef_residual shard is this worker's PADDED flat row;
            # both it and the padded flat_g view [n_chunks, chunk_pad] are
            # free reshapes — the whole EF+select phase is one kernel pass
            # (the kernel, the candidate top-k and the pack carry their
            # own scopes: ops/pallas_pack.py)
            r, cstate = spec.fused_ef_fn(
                state.ef_residual.reshape(n_chunks, chunk_pad),
                flat_g.reshape(n_chunks, chunk_pad),
                jnp.asarray(scale, jnp.float32), plan.buckets[0].k,
                state.comp_state[0])
            # chunk-local -> global offsets use the UNPADDED chunk size:
            # eligibility guarantees chunk_pad == chunk for multi-chunk
            # plans, and offset 0 for the single-bucket suffix pad. Invalid
            # sentinel slots (chunk_pad + off) land at/above n_total or on
            # a later chunk's first element with value 0.0 — dropped or a
            # +0.0 under the scatter-add exchanges either way.
            with jax.named_scope("pack"):
                offs = (jnp.arange(n_chunks, dtype=jnp.int32)
                        * chunk)[:, None]
                comp = CompressedGrad(
                    (r.compressed.indices + offs).reshape(-1),
                    r.compressed.values.reshape(-1))
                words = None
                if wire_fmt is not None and exchange == "allgather":
                    # wire-pack straight off the select pass's chunk-local
                    # output: the bucket-relative u16 IS the chunk-local
                    # index
                    words = pack_wire_words(
                        r.compressed.indices,
                        r.compressed.values).reshape(-1)
            return (comp, r.residual.reshape(-1),
                    r.num_selected.astype(jnp.int32).reshape(-1),
                    cstate, None, words)
        # a compressor without scopes of its own counts as a whole under
        # ef_select; the Pallas paths' inner cand_topk/pack win over it
        with jax.named_scope("ef_select"):
            acc = state.ef_residual + scale * flat_g
            comp, residual, nsel, cstate = compress_buckets(
                spec, plan, acc, comp_rng,
                state.comp_state[0] if spec.stateful else ())
        return comp, residual, nsel, cstate, acc, None

    def _make_sparse_step(use_pipeline: bool):
        """Build the sparse step program.

        ``use_pipeline`` selects the bucket-pipelined schedule (the
        double-buffered lax.scan — see the ``overlap`` docstring) vs. the
        historical sequential program; both are bit-identical in output.
        """

        def _gather(x):
            """Single issue point for the allgather-path payload collective
            (gklint collective-outside-pipeline funnel)."""
            with jax.named_scope("exchange"):
                return lax.all_gather(x, gather_axis, tiled=True)

        def _psum_outer(x):
            with jax.named_scope("exchange"):
                for a in outer_axes:
                    x = lax.psum(x, a)
            return x

        def _pipeline_launch(payload):
            """Issue the collective for ONE chunk's payload — called from
            the scan body for chunks 0..n-2 (overlapped behind the next
            chunk's compress) and once from the epilogue for the last
            chunk. gtopk launches its round-1 (stride 1) ppermute here;
            the remaining log2(P)-1 rounds need the merged buffer and run
            post-scan via butterfly_rounds."""
            if exchange == "gtopk":
                perm = [(j, j ^ 1) for j in range(gather_size)]
                with jax.named_scope("exchange"):
                    return tuple(lax.ppermute(p_, gather_axis, perm)
                                 for p_ in payload)
            return tuple(_gather(p_) for p_ in payload)

        def _chunk_payload(local_idx, val, off_i):
            """Wire payload for ONE chunk. Packed wire: the chunk-local
            index IS the u16 and the bucket id is the chunk's scan
            position, recovered structurally on assembly (same one-word
            format as encode_grouped, just chunk-at-a-time); legacy:
            global (i32, f32) pairs."""
            if wire_fmt is not None:
                return (wire_mod.encode_entries(local_idx, val),)
            return (local_idx + off_i, val)

        def _pipelined_phase(state: TrainState, flat_g: jax.Array, scale,
                             comp_rng: jax.Array):
            """EF accumulate + per-chunk compression with the collective
            for chunk i issued while chunk i+1 compresses. Returns
            ``(comp, residual, nsel, cstate, acc, recv)`` — the first five
            exactly as ``_compress_phase`` produces them (bit-identical:
            each chunk runs the SAME batched compressor machinery as the
            sequential uniform path, as a 1-row batch — every batched op
            is row-independent), plus ``recv``: the per-chunk received
            payload arrays stacked chunk-major ``[n_chunks, ...]`` for the
            exchange tail to reassemble.
            """
            n_chunks = len(plan.buckets)
            chunk, k = plan.buckets[0].size, plan.buckets[0].k
            offs = jnp.arange(n_chunks, dtype=jnp.int32) * chunk   # [n]
            if fused_ef is not None:
                # multi-chunk fused eligibility guarantees chunk_pad ==
                # chunk, so the padded rows ARE the chunks
                _nc, _c, chunk_pad = fused_ef
                xs = (state.ef_residual.reshape(n_chunks, chunk_pad),
                      flat_g.reshape(n_chunks, chunk_pad),
                      state.comp_state[0], offs)
                acc = None
            else:
                with jax.named_scope("ef_select"):
                    acc = state.ef_residual + scale * flat_g
                padded = n_chunks * chunk
                x = (jnp.pad(acc, (0, padded - acc.shape[0]))
                     if padded > acc.shape[0] else acc
                     ).reshape(n_chunks, chunk)
                st = (state.comp_state[0] if spec.stateful
                      else jnp.zeros((n_chunks,), jnp.float32))
                # same per-bucket rng derivation as compress_buckets'
                # uniform branch — identical draws, pipelined or not
                rngs = jax.vmap(lambda i: jax.random.fold_in(comp_rng, i))(
                    jnp.arange(n_chunks, dtype=jnp.uint32))
                xs = (x, st, rngs, offs)

            def compress_one(xi):
                if fused_ef is not None:
                    res_row, g_row, st_i, off_i = xi
                    r, st_new = spec.fused_ef_fn(
                        res_row[None], g_row[None],
                        jnp.asarray(scale, jnp.float32), k, st_i[None])
                else:
                    x_row, st_i, rng_i, off_i = xi
                    with jax.named_scope("ef_select"):
                        if spec.batched_fn is not None:
                            r, st_new = spec.batched_fn(
                                x_row[None], k, st_i[None], rng_i[None])
                        else:
                            r, st_new = jax.vmap(
                                lambda c, s, rg: _compressor_call(
                                    spec, c, k, s, rg))(
                                x_row[None], st_i[None], rng_i[None])
                return (r.compressed.indices[0], r.compressed.values[0],
                        r.residual[0],
                        r.num_selected.astype(jnp.int32).reshape(-1)[0],
                        st_new[0], off_i)

            # prologue: chunk 0 compresses with nothing in flight
            first = jax.tree.map(lambda a: a[0], xs)
            i0, v0, r0, ns0, s0, o0 = compress_one(first)
            carry0 = _chunk_payload(i0, v0, o0)
            rest = jax.tree.map(lambda a: a[1:], xs)

            def body(in_flight, xi):
                # the double buffer: issue chunk i's collective, THEN
                # compress chunk i+1 — no data dependence between the two,
                # so XLA overlaps the hop with the compress
                recv_i = _pipeline_launch(in_flight)
                li, v, res_row, ns, st_new, off_i = compress_one(xi)
                return (_chunk_payload(li, v, off_i),
                        ((li, v, res_row, ns, st_new), recv_i))

            last_payload, (outs, recv_rest) = lax.scan(body, carry0, rest)
            # epilogue: the last chunk's hop has no compress left to hide
            # behind — this is the irreducible exposed exchange tail
            recv_last = _pipeline_launch(last_payload)

            def _stack(first_leaf, rest_leaves):
                return jnp.concatenate([first_leaf[None], rest_leaves])

            idx2d = _stack(i0, outs[0])                 # [n, k] chunk-local
            val2d = _stack(v0, outs[1])                 # [n, k]
            res2d = _stack(r0, outs[2])
            nsel = _stack(ns0, outs[3])
            cstate = _stack(s0, outs[4])
            recv = jax.tree.map(
                lambda last_r, rest_r: jnp.concatenate([rest_r,
                                                        last_r[None]]),
                recv_last, recv_rest)
            comp = CompressedGrad((idx2d + offs[:, None]).reshape(-1),
                                  val2d.reshape(-1))
            residual = res2d.reshape(-1)
            if fused_ef is None:
                residual = residual[:acc.shape[0]]
            return comp, residual, nsel, cstate, acc, recv

        def sparse_step_fn(state: TrainState, batch: Any):
            data_rng, comp_rng = _step_rngs(state)
            loss, mstate, aux, new_carry, flat_g, unravel = _local_grads(
                state, batch, data_rng, ef_numel - n_total)
            scale = fold_lr(state.step) if fold_lr is not None else 1.0
            if use_pipeline:
                comp, residual, nsel, cstate, acc, recv = _pipelined_phase(
                    state, flat_g, scale, comp_rng)
                words = None
            else:
                comp, residual, nsel, cstate, acc, words = _compress_phase(
                    state, flat_g, scale, comp_rng)
                recv = None
            k_packed = comp.indices.shape[0]
            n_chunks = len(plan.buckets)
            # trace-time byte accounting: `overlapped` is the subset of
            # bytes_sent issued inside the scan body (chunks 0..n-2)
            overlapped = 0

            if exchange == "gtopk":
                # butterfly gTop-k: k entries per round, log2(P) rounds;
                # the global top-k is identical on every worker (gtopk.py).
                # EF keeps everything not globally selected.
                from .gtopk import (GtopkCommStats, butterfly_rounds,
                                    global_residual, gtopk_allreduce,
                                    merge_sparse)
                if use_pipeline:
                    # round 1 ran per-chunk inside the scan; reassemble the
                    # partner's buffer chunk-major (identical to the
                    # sequential round-1 ppermute output) and merge, then
                    # hand the merged set to rounds 2+. The local half is
                    # wire-roundtripped exactly where the sequential round
                    # quantizes before its merge.
                    if wire_fmt is not None:
                        rel2d, dval2d = wire_mod.decode_entries(recv[0])
                        o_idx = (rel2d + (jnp.arange(
                            n_chunks, dtype=jnp.int32)
                            * plan.buckets[0].size)[:, None]).reshape(-1)
                        o_val = dval2d.reshape(-1)
                        local_val = wire_mod.bf16_roundtrip(comp.values)
                        round1_bytes = k_packed * 4
                    else:
                        o_idx = recv[0].reshape(-1)
                        o_val = recv[1].reshape(-1)
                        local_val = comp.values
                        round1_bytes = k_packed * 8
                    with jax.named_scope("exchange"):
                        m_idx, m_val = merge_sparse(
                            comp.indices, local_val, o_idx, o_val, k_packed)
                        m_idx, m_val, tail_bytes = butterfly_rounds(
                            m_idx, m_val, mesh.size, gather_axis, wire_fmt,
                            start_round=1)
                    overlapped = round1_bytes * (n_chunks - 1) // n_chunks
                    gcomp = CompressedGrad(m_idx, m_val)
                    n_rounds = int(math.log2(mesh.size))
                    comm = GtopkCommStats(
                        bytes_sent=round1_bytes + tail_bytes,
                        rounds=n_rounds,
                        entries_per_round=k_packed,
                        wire_format=(wire_fmt.name if wire_fmt is not None
                                     else wire_mod.WIRE_LEGACY),
                        overlapped_bytes=overlapped, pipelined=True,
                        bytes_per_round=(tail_bytes // (n_rounds - 1)
                                         if n_rounds > 1 else round1_bytes))
                else:
                    # trace-time count of the buffers actually ppermuted
                    # (shape x itemsize per round) — measured, not a formula
                    with jax.named_scope("exchange"):
                        gcomp, comm = gtopk_allreduce(
                            comp, mesh.size, gather_axis, wire=wire_fmt)
                with jax.named_scope("scatter"):
                    # the /P average rides the k-sized VALUES, not the
                    # n-sized dense buffer: one full read+write pass saved
                    # (r4 floor)
                    gcomp = gcomp._replace(
                        values=gcomp.values / _all_axes_size())
                    if flat_opt is None:
                        dense = decompress(gcomp, n_total, grad_dtype)
                    residual = global_residual(acc, gcomp)
                bytes_sent = jnp.float32(comm.bytes_sent)
            elif wire_fmt is not None:
                # packed wire exchange (parallel/wire.py): u32 words — u16
                # bucket-relative index | bf16 value, half the (i32, f32)
                # payload. The receiver reconstructs global indices from
                # (position-derived bucket id, relative offset); no i32
                # index buffer is gathered or materialized on the wire.
                if use_pipeline:
                    # [n, P*k] chunk-major gathers -> the device-major
                    # [P, n, k] flat buffer the one-shot all_gather makes
                    g_words = (recv[0].reshape(
                        n_chunks, gather_size, plan.buckets[0].k)
                        .transpose(1, 0, 2).reshape(-1))
                    overlapped = (n_chunks - 1) * plan.buckets[0].k * 4
                    bytes_count = k_packed * 4
                else:
                    if words is None:   # unfused: encode from global comp
                        with jax.named_scope("pack"):
                            words = wire_mod.encode_grouped(comp, wire_fmt)
                    g_words = _gather(words)
                    # measured from the concrete packed buffer handed to
                    # the collective — never a closed-form estimate
                    bytes_count = words.size * words.dtype.itemsize
                with jax.named_scope("scatter"):
                    g_comp = wire_mod.decode_grouped(g_words, wire_fmt,
                                                     k_packed)
                    g_idx = g_comp.indices
                    g_val = g_comp.values / _all_axes_size()
                    if flat_opt is None:
                        dense = decompress(CompressedGrad(g_idx, g_val),
                                           n_total, grad_dtype)
                if flat_opt is None:
                    dense = _psum_outer(dense)
                # EF absorbs the bf16 rounding on-device in f32: the
                # committed residual gets back exactly (value - decoded
                # value) at each sent index, so the quantization error
                # never accumulates. mode='drop' for pad-chunk slots
                # at/above the residual length.
                with jax.named_scope("scatter"):
                    q_err = comp.values - wire_mod.bf16_roundtrip(
                        comp.values)
                    residual = residual.at[comp.indices].add(q_err,
                                                             mode="drop")
                bytes_sent = jnp.float32(bytes_count)
            else:
                # allgather of the packed pairs over the (ICI) gather axis,
                # scatter-summed dense; hierarchical meshes psum the dense
                # partial across the outer (DCN) axes (collectives.py). The
                # /P average is applied to the k-sized gathered values
                # BEFORE the scatter — dividing the n-sized dense buffer
                # costs a full read+write pass; each outer-axis partial is
                # already /P-scaled so the psum-summed result is identical.
                if use_pipeline:
                    k = plan.buckets[0].k
                    with jax.named_scope("scatter"):
                        g_idx = (recv[0].reshape(n_chunks, gather_size, k)
                                 .transpose(1, 0, 2).reshape(-1))
                        g_val = (recv[1].reshape(n_chunks, gather_size, k)
                                 .transpose(1, 0, 2).reshape(-1)
                                 / _all_axes_size())
                    overlapped = (n_chunks - 1) * k * 8
                else:
                    g_idx = _gather(comp.indices)
                    g_val = _gather(comp.values)
                    with jax.named_scope("scatter"):
                        g_val = g_val / _all_axes_size()
                if flat_opt is None:
                    with jax.named_scope("scatter"):
                        dense = decompress(CompressedGrad(g_idx, g_val),
                                           n_total, grad_dtype)
                    dense = _psum_outer(dense)
                # measured from the concrete (idx, val) buffers handed to
                # the collectives (same count the old closed form produced)
                bytes_sent = jnp.float32(
                    comp.indices.size * comp.indices.dtype.itemsize
                    + comp.values.size * comp.values.dtype.itemsize)

            if flat_opt is not None:
                # scatter the gathered pairs straight into the decayed
                # momentum (flat_opt.py): no dense gradient buffer exists
                if exchange == "gtopk":
                    g_idx, g_val = gcomp.indices, gcomp.values

                def update(params, opt_state):
                    params, m = flat_opt.sparse_step(
                        params, opt_state["m"], g_idx.reshape(-1), g_val,
                        state.step)
                    return params, {"m": m}
            else:
                update = _optax_update(dense, unravel)
            cnt, grad_norm = _grad_count_and_norm(loss, flat_g)
            new_state, skipped, nonfinite = _commit(
                cnt, state, mstate, residual, new_carry,
                cstate[None, :] if spec.stateful else state.comp_state,
                update)
            # on-device comms/compression accounting (telemetry): one pmean
            # of the per-bucket count vector serves num_selected, the
            # achieved density, AND the per-bucket breakdown; the EF norm
            # reads the COMMITTED residual so a guard-skipped step reports
            # the state that actually persists
            with jax.named_scope("step_metrics"):
                sel_per_bucket = _pmean(nsel.astype(jnp.float32))
                num_selected = jnp.sum(sel_per_bucket)
            return new_state, StepMetrics(
                loss, aux, grad_norm,
                num_selected, bytes_sent, skipped, nonfinite,
                achieved_density=num_selected / n_total,
                ef_norm=_ef_norm(new_state.ef_residual),
                sel_per_bucket=sel_per_bucket,
                overlapped_bytes_sent=jnp.float32(overlapped),
                pipeline_chunks=jnp.float32(
                    n_chunks if use_pipeline else 0),
                comm_rounds=jnp.float32(
                    int(math.log2(mesh.size)) if exchange == "gtopk"
                    and mesh.size > 1 else 1))

        return sparse_step_fn

    sparse_step_fn = _make_sparse_step(pipelined)

    def dense_step_fn(state: TrainState, batch: Any):
        data_rng, _ = _step_rngs(state)
        loss, mstate, aux, new_carry, flat_g, unravel = _local_grads(
            state, batch, data_rng)
        scale = fold_lr(state.step) if fold_lr is not None else 1.0
        with jax.named_scope("exchange"):
            dense = scale * flat_g
            for a in axes:
                dense = lax.psum(dense, a)
            dense = dense / _all_axes_size()
        # Warm-up is compression-off: the EF residual is untouched (and zero
        # if warm-up precedes any sparse step), matching SURVEY.md §2.3.
        if flat_opt is not None:
            def update(params, opt_state):
                params, m = flat_opt.dense_step(params, opt_state["m"],
                                                dense, state.step)
                return params, {"m": m}
        else:
            update = _optax_update(dense, unravel)
        cnt, grad_norm = _grad_count_and_norm(loss, flat_g)
        new_state, skipped, nonfinite = _commit(
            cnt, state, mstate, state.ef_residual, new_carry,
            state.comp_state, update)
        return new_state, StepMetrics(
            loss, aux, grad_norm,
            jnp.float32(n_total), jnp.float32(n_total * 4), skipped,
            nonfinite,
            achieved_density=jnp.float32(1.0),
            ef_norm=_ef_norm(new_state.ef_residual),
            sel_per_bucket=jnp.asarray(bucket_sizes_f32, jnp.float32),
            overlapped_bytes_sent=jnp.float32(0),
            pipeline_chunks=jnp.float32(0),
            comm_rounds=jnp.float32(1))

    if sp_axis is None:
        batch_spec = P(axes)        # leading dim sharded over every dp axis
    else:
        # dim 0 (examples) over the dp axes, dim 1 (sequence) over sp
        batch_spec = P(axes[:-1] or None, axes[-1])
    # Pytree-prefix specs: everything in TrainState is replicated except the
    # per-worker ef_residual (flat, contiguous per-worker slices on dim 0)
    # and the recurrent
    # carry (batch-dim sharded, like the batch itself).
    state_spec = TrainState(step=P(), params=P(), model_state=P(),
                            opt_state=P(), ef_residual=P(axes), rng=P(),
                            carry=P(axes) if recurrent else P(),
                            comp_state=P(axes) if spec.stateful else P())

    def _smap(fn):
        return shard_map(
            fn, mesh=mesh,
            in_specs=(state_spec, batch_spec),
            out_specs=(state_spec, P()),
            check_vma=False,
        )

    def _wrap(fn):
        return jax.jit(_smap(fn), donate_argnums=(0,))

    def init_state(params: Any, rng: jax.Array,
                   model_state: Any = None, carry: Any = ()) -> TrainState:
        """A fresh TrainState, created UNDER the step's shardings: the
        replicated leaves on every device of the mesh, the per-worker
        leaves (EF residual, compressor state, carry) as one shard per
        worker — so no device ever holds all P residual rows and the first
        step's donation is not spent on a re-layout."""
        numel = sum(x.size for x in jax.tree_util.tree_leaves(params))
        if numel != n_total:
            raise ValueError(
                f"bucket plan built for {n_total} params, model has "
                f"{numel}")
        if recurrent and not jax.tree_util.tree_leaves(carry):
            raise ValueError(
                "recurrent=True needs an initial carry (model.initial_carry)")
        replicated = NamedSharding(mesh, P())
        per_worker = NamedSharding(mesh, P(axes))

        def place(tree, sharding):
            # The step functions donate their input state; copy so the
            # caller's buffers are never invalidated (and two states can
            # share an init pytree) — device_put alone may alias them.
            return jax.device_put(jax.tree.map(jnp.copy, tree), sharding)

        params = place(params, replicated)
        return TrainState(
            step=jax.device_put(jnp.int32(0), replicated),
            params=params,
            model_state=place({} if model_state is None else model_state,
                              replicated),
            opt_state=jax.device_put(
                flat_opt.init(n_total, grad_dtype) if flat_opt is not None
                else optimizer.init(params), replicated),
            # padded per-worker rows on the fused-EF path (ef_numel ==
            # n_total otherwise); the pad starts zero and stays zero
            ef_residual=jnp.zeros((mesh.size * ef_numel,), grad_dtype,
                                  device=per_worker),
            rng=place(rng, replicated),
            carry=place(carry, per_worker if recurrent else replicated),
            comp_state=(jnp.full((mesh.size, len(plan.buckets)),
                                 spec.init_state, jnp.float32,
                                 device=per_worker)
                        if spec.stateful else ()),
        )

    return DPTrainStep(_wrap(sparse_step_fn), _wrap(dense_step_fn),
                       init_state, plan, mesh, ef_numel,
                       wire_fmt.name if wire_fmt is not None
                       else wire_mod.WIRE_LEGACY,
                       "pipelined" if pipelined else "off", kernel_mode)
