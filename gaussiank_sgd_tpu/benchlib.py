"""Shared benchmark machinery for bench.py and analysis/bench_matrix.py.

Measurement methodology (see bench.py docstring): a single dispatch is
dominated by host latency at millisecond step times, so every timing runs N
steps inside ONE jitted ``fori_loop`` (DPTrainStep.make_multi_step) and
fences with a scalar ``device_get``; dense and sparse variants are timed in
interleaved, rotated rounds (device speed drifts over minutes) and each
variant reports its min across rounds.
"""

from __future__ import annotations

import time
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import optax


# Dense bf16 peak FLOP/s per chip, keyed by the EXACT jax ``device_kind``
# (spellings as in jax's own pallas/mosaic/tpu_info.py). Source of every
# figure: the Google Cloud TPU documentation page of that generation.
# MFU here = model FLOPs / (step time * peak).
PEAK_FLOPS_BY_KIND = {
    "TPU v4": 275e12,        # "TPU v4"
    "TPU v5 lite": 197e12,   # "TPU v5e"
    "TPU v5": 459e12,        # "TPU v5p"
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,   # "TPU v6e" (Trillium)
}


def device_peak_flops(device=None) -> Optional[float]:
    """bf16 peak FLOP/s of the chip. None off-TPU (no MFU on CPU — the
    field is absent, "not measured"); a TPU whose ``device_kind`` is not in
    the table raises — a missing peak is an error, never another
    generation's figure."""
    d = jax.devices()[0] if device is None else device
    if d.platform != "tpu":
        return None
    if d.device_kind not in PEAK_FLOPS_BY_KIND:
        raise KeyError(
            f"no peak FLOP/s on record for TPU device_kind "
            f"{d.device_kind!r}; add it to benchlib.PEAK_FLOPS_BY_KIND "
            f"with its source")
    return PEAK_FLOPS_BY_KIND[d.device_kind]


def program_flops(jitted, *args) -> Optional[float]:
    """FLOP count of a jitted program from XLA's HLO cost analysis.

    This is an *analytic* count computed from HLO op shapes (conv/matmul
    terms dominate), not a measurement — the denominator-independent FLOPs
    model VERDICT r2 item 2 asks for, with the advantage over hand formulas
    that it is exact for the program actually compiled. Lowers and compiles
    ``jitted`` for ``args`` — a cache hit when that program already ran, a
    full compile when it has not. Errors propagate; None only when the
    backend's analysis reports no FLOPs.
    """
    flops = jitted.lower(*args).compile().cost_analysis().get("flops", 0.0)
    return float(flops) if flops else None


def mfu(flops_per_step: Optional[float], step_seconds: float,
        peak: Optional[float]) -> Optional[float]:
    """Model-FLOPs utilization; None when FLOPs or peak are unavailable."""
    if not flops_per_step or not peak or step_seconds <= 0:
        return None
    return flops_per_step / (step_seconds * peak)


def paired_delta_ms(rounds: dict, a: str, b: str) -> Optional[float]:
    """Median over rounds of per-round (a_r - b_r), in ms.

    THE drift-robust phase-delta estimator (shared by sparse_ablation.py
    and bench_matrix.py): min-of-rounds differences between variants can
    land in different drift regimes of the shared chip and produce
    physically impossible (negative) decompositions — the first r4
    ablation run did exactly that. Every variant runs inside every
    rotated round, so paired medians cancel the drift.

    Returns None (instead of silently zip-truncating) when the two
    variants have different round counts — a partial/crashed run re-read
    from artifacts would otherwise misalign the pairing and corrupt the
    drift-cancelling property (ADVICE r4).
    """
    import statistics

    ra, rb = rounds.get(a, []), rounds.get(b, [])
    if not ra or len(ra) != len(rb):
        return None
    pairs = [1e3 * (x - y) for x, y in zip(ra, rb)]
    return round(statistics.median(pairs), 3)


def noise_floored_delta_ms(rounds: dict, a: str, b: str) -> Optional[float]:
    """``paired_delta_ms`` that never reports a negative duration.

    A phase delta is a DURATION — a physical quantity that cannot be
    negative. The paired-median estimator still goes slightly negative
    when the true delta is smaller than the per-round timing noise (the
    r5 matrix printed select_pack_ms = -0.1 for cells where select+pack
    is cheaper than one round's jitter — VERDICT r5 weak #5). The honest
    report for such a cell is "below measurement noise", not a negative
    number that a reader must know to discard.

    Rule: returns the paired median when it exceeds the noise floor —
    the median absolute deviation of the per-round paired deltas (the
    same samples, so the floor tracks the actual round-to-round jitter
    of this cell, not a global constant) — and None otherwise. Callers
    render None as "< noise". Single-round runs have no dispersion
    estimate, so only the sign rule applies there.
    """
    import statistics

    ra, rb = rounds.get(a, []), rounds.get(b, [])
    if not ra or len(ra) != len(rb):
        return None
    pairs = [1e3 * (x - y) for x, y in zip(ra, rb)]
    med = statistics.median(pairs)
    if med <= 0:
        return None
    if len(pairs) >= 2:
        mad = statistics.median([abs(p - med) for p in pairs])
        if med <= mad:
            return None
    return round(med, 3)


def ablation_specs():
    """Probe compressors that run a PREFIX of the sparse pipeline, for
    drift-free phase decomposition (VERDICT r3 item 6; the reference
    logged io/fwd/bwd/comm per display interval — SURVEY.md §5 Tracing).

    ``ef_only``  — EF accumulate + exchange of a fixed k-slice (no
                   selection): the floor every sparse step pays. Its delta
                   over the dense step is the exchange cost; a real
                   selector's delta over it is the select+pack cost.
    ``sel_nores`` — + abs/cast/approx_max_k/gather but NO residual
                   scatter (EF-INCORRECT, measurement only).

    Both are bench probes, not registry entries: they must never be
    reachable from training configs.
    """
    import jax

    from .compressors.base import CompressedGrad, CompressResult
    from .compressors.registry import CompressorSpec

    def ef_only(acc, k, rng=None):
        idx = jnp.arange(k, dtype=jnp.int32)
        val = acc[:k]
        residual = acc.at[idx].set(0.0)
        return CompressResult(CompressedGrad(idx, val), residual,
                              jnp.asarray(k, jnp.int32))

    def sel_nores(acc, k, rng=None):
        mag = jnp.abs(acc).astype(jnp.bfloat16)
        _, idx = jax.lax.approx_max_k(mag, k, recall_target=0.95)
        idx = idx.astype(jnp.int32)
        val = acc[idx]
        return CompressResult(CompressedGrad(idx, val), acc,
                              jnp.asarray(k, jnp.int32))

    return {
        "ef_only": CompressorSpec("ef_only", ef_only, False, True,
                                  lambda k: k),
        "sel_nores": CompressorSpec("sel_nores", sel_nores, False, True,
                                    lambda k: k),
    }


def make_batch(spec, batch_size: int, rng=None):
    """Synthesize a (x, y) batch matching the model task's shapes."""
    rng = jax.random.PRNGKey(0) if rng is None else rng
    r1, r2 = jax.random.split(rng)
    if spec.task == "classify":
        x = jax.random.normal(r1, (batch_size,) + spec.input_shape,
                              jnp.float32)
        y = jax.random.randint(r2, (batch_size,), 0, spec.num_classes)
    elif spec.task == "lm":
        t = spec.input_shape[0]
        x = jax.random.randint(r1, (batch_size, t), 0, spec.num_classes)
        y = jax.random.randint(r2, (batch_size, t), 0, spec.num_classes)
    elif spec.task == "seq2seq":
        t = spec.input_shape[0]
        x = jax.random.randint(r1, (batch_size, t), 1, spec.num_classes)
        y = jax.random.randint(r2, (batch_size, t), 1, spec.num_classes)
    elif spec.task == "ctc":
        x = jax.random.normal(r1, (batch_size,) + spec.input_shape,
                              jnp.float32)
        y = jax.random.randint(r2, (batch_size, 16), 1, spec.num_classes)
    else:
        raise ValueError(spec.task)
    return x, y


def _run_once(multi_step, mk_state, batch, n_steps):
    state = mk_state()
    t0 = time.perf_counter()
    state, m = multi_step(state, batch)
    _ = float(m.loss)                          # host read = true fence
    return (time.perf_counter() - t0) / n_steps


def _time_programs(programs, batch, n_steps, rounds, windows):
    """Interleaved rotated-round timing over a dict of
    ``name -> (multi_step, mk_state)`` programs (the shared inner loop of
    ``bench_model`` and ``bench_overlap``). Returns ``(min_times,
    round_times, window_times)`` — per-variant min seconds, pooled
    per-round samples, and the same samples grouped per window."""
    out = {k: float("inf") for k in programs}
    round_times = {k: [] for k in programs}
    window_times = {k: [] for k in programs}
    names = list(programs)
    for w in range(max(1, int(windows))):
        wt = {k: [] for k in programs}
        for r in range(rounds):
            # rotate the within-round order (continuously across windows)
            # — a fixed order hands whatever first-slot penalty exists to
            # the same variant every round
            g = w * rounds + r
            for name in names[g % len(names):] + names[:g % len(names)]:
                fn, mk = programs[name]
                t = _run_once(fn, mk, batch, n_steps)
                wt[name].append(t)
                round_times[name].append(t)
                out[name] = min(out[name], t)
        for k in programs:
            window_times[k].append(wt[k])
    return out, round_times, window_times


def bench_model(model: str, dataset: str, batch_size: int, density: float,
                compressors: Sequence[str], n_steps: int, rounds: int = 8,
                windows: int = 1,
                include_dense: bool = True, model_kwargs: Optional[dict] = None,
                dtype=jnp.bfloat16, bucket_policy: str = "greedy",
                bucket_size: Optional[int] = None) -> Dict[str, float]:
    """Per-step seconds for the dense program + each compressor's sparse
    program on one model. Timing keys: 'dense' + compressor names.
    Underscore-prefixed keys are metadata, NOT timings: ``_rounds``
    (per-round samples pooled over all windows, dict of lists),
    ``_windows`` (the same samples grouped per measurement window:
    dict of ``windows`` lists of ``rounds`` samples — consumers compute
    per-window paired medians from it, ISSUE 6 measurement-power
    satellite), ``_dense_step_flops`` and
    ``_peak_flops`` (MFU inputs), ``_exchange`` (per-compressor wire
    accounting: the build's wire format name, its measured per-step
    ``bytes_sent`` drained from the warm run's StepMetrics, and the
    plan's total_k — the bytes are the concrete exchanged buffers'
    count, parallel/wire.py) — consumers iterating the dict must
    filter them.

    ``windows``: repeat the whole ``rounds``-round interleaved block this
    many times. Windows are farther apart in wall-clock than rounds, so
    slow machine drift (thermal state, co-tenant load) lands BETWEEN
    windows; a claim that holds for the min across window medians is one
    that survives re-measurement.

    ``bucket_policy``/``bucket_size``: the selection-unit plan (SURVEY.md
    §2.3 bucketing). The VERDICT-r2 scaling recipe for 20M+ LM models is
    ``bucket_policy='uniform', bucket_size=1<<22`` — per-chunk vmapped
    selection instead of one whole-model pass."""
    from .compressors import get_compressor
    from .models import get_model
    from .parallel.bucketing import plan_for_params
    from .parallel.flat_opt import FlatSGDM
    from .parallel.mesh import data_parallel_mesh, shard_batch
    from .parallel.trainstep import build_dp_train_step
    from .training.losses import make_loss_fn

    mesh = data_parallel_mesh()
    spec = get_model(model, dataset, dtype=dtype, **(model_kwargs or {}))
    rng = jax.random.PRNGKey(0)
    x, y = make_batch(spec, batch_size)
    recurrent = model == "lstm"
    init_inputs = ((x[:2], y[:2]) if spec.task == "seq2seq" else (x[:2],))
    variables = spec.module.init({"params": rng}, *init_inputs, train=False)
    params = variables["params"]
    mstate = {k: v for k, v in variables.items() if k != "params"}
    plan = plan_for_params(params, density, bucket_size,
                           policy=bucket_policy)
    batch = shard_batch(mesh, (x, y))
    carry = (spec.module.initial_carry(batch_size) if recurrent else ())

    probes = ablation_specs()
    programs = {}
    exchange_meta: Dict[str, dict] = {}
    dense_ts = dense_mk = None
    for name in compressors:
        comp = probes.get(name) or get_compressor(name, density=density)
        ts = build_dp_train_step(
            make_loss_fn(spec, recurrent=recurrent),
            None, comp, plan, mesh,
            recurrent=recurrent,
            # the flat sparse-aware update (parallel/flat_opt.py) — the
            # framework's production SGD path, so the bench times it
            flat_opt=FlatSGDM(lr=0.1, momentum=0.9))

        def mk(ts=ts):
            return ts.init_state(params, jax.random.PRNGKey(2),
                                 model_state=mstate, carry=carry)

        if include_dense and "dense" not in programs:
            programs["dense"] = (ts.make_multi_step("dense", n_steps), mk)
            dense_ts, dense_mk = ts, mk
        programs[name] = (ts.make_multi_step("sparse", n_steps), mk)
        exchange_meta[name] = {"wire_format": ts.wire_format,
                               "overlap": ts.overlap,
                               "total_k": int(ts.plan.total_k)}

    for name, (fn, mk) in programs.items():   # compile + warm
        st, m = fn(mk(), batch)
        _ = float(m.loss)
        if name in exchange_meta:
            # measured per-step exchange payload, drained once from the
            # warm run — the jitted step counts its own concrete buffers
            exchange_meta[name]["bytes_sent"] = int(m.bytes_sent)

    out, round_times, window_times = _time_programs(
        programs, batch, n_steps, rounds, windows)
    # per-round samples for median/dispersion reporting (VERDICT r2 item 6:
    # min-of-rounds alone lets drift-band artifacts carry a headline), plus
    # the same samples grouped per window (min-across-window-medians
    # reporting, ISSUE 6)
    out["_rounds"] = round_times
    out["_windows"] = window_times
    out["_exchange"] = exchange_meta
    if include_dense and dense_ts is not None:
        # absolute-performance leg (VERDICT r2 item 2): the dense step's
        # HLO FLOP count is the model-FLOPs numerator for every variant's
        # MFU (sparse MFU counts useful model math per second; selection
        # overhead shows up as a lower MFU, not a bigger numerator)
        out["_dense_step_flops"] = program_flops(
            dense_ts.dense_step, dense_mk(), batch)
        out["_peak_flops"] = device_peak_flops()
    return out


def bench_overlap(model: str, dataset: str, batch_size: int,
                  density: float, compressor: str, n_steps: int,
                  rounds: int = 4, windows: int = 1,
                  bucket_size: int = 1 << 22,
                  model_kwargs: Optional[dict] = None,
                  dtype=jnp.bfloat16) -> Dict[str, object]:
    """The ISSUE-7 overlap arm: the SAME model/selector timed under both
    step schedules on one pipeline-eligible uniform bucket plan, each
    with its exchange-ablated timing twin, all four programs interleaved
    in the same rotated rounds so the off-vs-auto comparison and both
    ``exposed_exchange_ms`` estimates are drift-cancelled.

    Timing keys: ``seq``/``seq_noexch`` (overlap='off') and
    ``pipe``/``pipe_noexch`` (overlap='auto'). ``exposed_exchange_ms``
    per schedule = ``noise_floored_delta_ms`` of the variant against its
    twin (None = below this cell's round-to-round noise). ``_meta``
    carries the builds' reported schedules (the 'auto' build must say
    'pipelined' — callers assert eligibility), wire format, per-step
    bytes and the pipelined build's ``overlapped_bytes_sent``."""
    from .compressors import get_compressor
    from .models import get_model
    from .parallel.bucketing import plan_for_params
    from .parallel.flat_opt import FlatSGDM
    from .parallel.mesh import data_parallel_mesh, shard_batch
    from .parallel.trainstep import build_dp_train_step
    from .training.losses import make_loss_fn

    mesh = data_parallel_mesh()
    spec = get_model(model, dataset, dtype=dtype, **(model_kwargs or {}))
    rng = jax.random.PRNGKey(0)
    x, y = make_batch(spec, batch_size)
    recurrent = model == "lstm"
    init_inputs = ((x[:2], y[:2]) if spec.task == "seq2seq" else (x[:2],))
    variables = spec.module.init({"params": rng}, *init_inputs, train=False)
    params = variables["params"]
    mstate = {k: v for k, v in variables.items() if k != "params"}
    plan = plan_for_params(params, density, bucket_size, policy="uniform")
    batch = shard_batch(mesh, (x, y))
    carry = (spec.module.initial_carry(batch_size) if recurrent else ())
    loss_fn = make_loss_fn(spec, recurrent=recurrent)

    programs = {}
    meta: Dict[str, object] = {"bucket_size": bucket_size,
                               "n_buckets": len(plan.buckets),
                               "total_k": int(plan.total_k)}
    for arm, overlap in (("seq", "off"), ("pipe", "auto")):
        comp = get_compressor(compressor, density=density)
        ts = build_dp_train_step(
            loss_fn, None, comp, plan, mesh, recurrent=recurrent,
            flat_opt=FlatSGDM(lr=0.1, momentum=0.9), overlap=overlap)
        meta[f"{arm}_overlap"] = ts.overlap
        meta.setdefault("wire_format", ts.wire_format)

        def mk(ts=ts):
            return ts.init_state(params, jax.random.PRNGKey(2),
                                 model_state=mstate, carry=carry)

        programs[arm] = (ts.make_multi_step("sparse", n_steps), mk)
        programs[f"{arm}_noexch"] = (
            ts.make_multi_step("sparse_noexch", n_steps), mk)

    for arm in ("seq", "pipe"):                # compile + warm, drain meta
        fn, mk = programs[arm]
        st, m = fn(mk(), batch)
        _ = float(m.loss)
        meta[f"{arm}_bytes_sent"] = int(m.bytes_sent)
        if arm == "pipe":
            meta["overlapped_bytes_sent"] = int(m.overlapped_bytes_sent)
        fn_nx, mk_nx = programs[f"{arm}_noexch"]
        st, m = fn_nx(mk_nx(), batch)
        _ = float(m.loss)

    out, round_times, window_times = _time_programs(
        programs, batch, n_steps, rounds, windows)
    result: Dict[str, object] = {k: out[k] for k in programs}
    result["_rounds"] = round_times
    result["_windows"] = window_times
    result["_meta"] = meta
    result["exposed_exchange_ms"] = {
        "seq": noise_floored_delta_ms(round_times, "seq", "seq_noexch"),
        "pipe": noise_floored_delta_ms(round_times, "pipe", "pipe_noexch"),
    }
    return result
