"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the path a user runs — ``gaussiank_sgd_tpu.train`` -> ``Trainer`` ->
``build_dp_train_step`` — once, in ONE process (a chip belongs to one process:
no subprocess here), at the full width of VGG-16 / CIFAR-10 (BASELINE config
2: 14 986 698 parameters, batch 128 per worker, bf16 compute, density 0.001),
on seeded random weights and synthetic data. Legs, each of which must pass:

  kernel    the fused EF+select kernel, Mosaic-compiled, on a VGG-sized
            buffer: against plain ``jnp`` and against the same kernel under
            the Pallas interpreter; then EF exactness through the wrapper
  trainer   24 steps through ``train.make_trainer(...).fit()``: 4 dense
            warm-up steps, 20 sparse steps with the Mosaic kernel in the
            step, eval passes, a sealed checkpoint, an ``mfu`` field
  resume    a second Trainer resumes that checkpoint at step 24, takes 4
  four      (only where ``jax.device_count() >= 4``) the trainer leg on a
            4-worker mesh: where the state lives, which collectives the
            compiled steps hold, per-device peak memory

It refuses to run — exit code != 0, no result line — unless JAX's default
backend is a TPU, and when ``GKSGD_FORCE_VIRTUAL_CPU`` is set. Any failed
check raises; nothing is caught. The seconds it prints are set-up and sanity
figures for this run, not benchmark metrics. The last line of stdout is the
result: ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count":
...}}``, the device as JAX reports it.

    python chip_smoke.py            # on the chip (through the chip tool)
"""

from __future__ import annotations

import collections
import importlib.metadata
import json
import os
import shutil
import statistics
import tempfile
import time

if os.environ.get("GKSGD_FORCE_VIRTUAL_CPU", "").strip():
    raise SystemExit("chip_smoke: GKSGD_FORCE_VIRTUAL_CPU is set — this is "
                     "the chip check; unset it")

import jax

if jax.default_backend() != "tpu":
    raise SystemExit(f"chip_smoke: needs a TPU; JAX's default backend is "
                     f"{jax.default_backend()!r}. Nothing was run.")

import jax.numpy as jnp
import numpy as np

from gaussiank_sgd_tpu import train
from gaussiank_sgd_tpu.compile_cache import enable_compile_cache
from gaussiank_sgd_tpu.compressors.base import CompressedGrad, decompress
from gaussiank_sgd_tpu.ops.pallas_pack import (
    _LANES, _chunk_geometry, fused_ef_select_candidates_chunked,
    gaussian_fused_ef_compress_batched)
from gaussiank_sgd_tpu.parallel import wire
from gaussiank_sgd_tpu.training.checkpoint import latest_checkpoint

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(HERE, "exp_configs",
                      "config2_vgg16_cifar10_gaussian.json")
VGG16_PARAMS = 14_986_698
VGG16_EF_NUMEL = 15_073_280        # the whole-model bucket, block-padded
DENSITY = 0.001
WARMUP_STEPS, LOG_EVERY = 4, 4

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"
compile_secs: dict = collections.defaultdict(float)   # program -> seconds
                                    # (a cache hit counts its retrieval)
cache_events: collections.Counter = collections.Counter()


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke: FAILED: {what}")
    say(f"ok: {what}")


def _on_duration(event, duration, **kw):
    if event == _COMPILE_EVENT:
        compile_secs[kw.get("fun_name", "?")] += duration
    elif event == _SAVED_EVENT:
        cache_events["compile_s_saved"] += duration


def _on_event(event, **kw):
    if event.startswith("/jax/compilation_cache/cache_"):
        cache_events[event.rsplit("/", 1)[1]] += 1


def _n_cache_files(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


# --------------------------------------------------------------- kernel leg

def kernel_leg() -> None:
    n, k = VGG16_EF_NUMEL, 14_987
    R, seg, bpc, nc = _chunk_geometry(n, DENSITY)
    check(bpc * R * _LANES == n, f"geometry R={R} seg={seg} blocks={bpc} "
                                 f"tiles [1, {n}] exactly")
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    res = 0.5 * jax.random.normal(k1, (1, n), jnp.float32)
    g = jax.random.normal(k2, (1, n), jnp.float32)
    # a power of two: the product is exact, so a fused and an unfused
    # multiply-add agree bitwise and the comparison below is about the
    # kernel, not about FMA contraction in the reference
    scale = jnp.float32(0.5)
    # |N(0, 1/2)| > 2.327 has two-sided tail mass ~0.001 -> ~k entries
    t = jnp.full((1,), 2.327, jnp.float32)

    def run(interpret):
        return jax.jit(lambda r_, g_, s_, t_: (
            fused_ef_select_candidates_chunked(
                r_, g_, s_, t_, DENSITY, interpret=interpret)))(
                    res, g, scale, t)

    t0 = time.perf_counter()
    acc, vals, idxs, counts = jax.block_until_ready(run(False))
    say(f"mosaic kernel compiled+ran in {time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    acc_i, vals_i, idxs_i, counts_i = jax.block_until_ready(run(True))
    say(f"interpreted kernel compiled+ran in {time.perf_counter() - t0:.1f}s")

    # --- against plain jnp -------------------------------------------------
    acc_ref = res + scale * g
    check(bool(jnp.array_equal(acc, acc_ref)),
          "kernel acc == res + scale*g bitwise")
    mask = jnp.abs(acc_ref) > t[0]
    want = int(jnp.sum(mask))
    check(int(counts[0]) == want and 0.5 * k < want < 2 * k,
          f"kernel count {int(counts[0])} == sum(|acc| > t) {want}")
    # per-(segment, lane) cell: a candidate exists iff the cell holds an
    # above-threshold entry; it is one of the cell's own entries; and its
    # magnitude is the cell's maximum up to the ranking key's truncation
    # (low log2(seg) mantissa bits carry the row id)
    cell_abs = jnp.where(mask, jnp.abs(acc_ref), 0.0).reshape(
        -1, seg, _LANES)
    cell_max = jnp.max(cell_abs, axis=1).reshape(-1)
    v, i = vals[0], idxs[0]
    valid = v != 0
    check(bool(jnp.array_equal(valid, cell_max > 0)),
          f"a candidate in exactly the {int(jnp.sum(valid))} non-empty cells")
    check(bool(jnp.all(jnp.where(valid, acc_ref[0, i] == v, True))),
          "every candidate value is acc[index] exactly")
    cell_of = (i // _LANES // seg) * _LANES + i % _LANES
    check(bool(jnp.all(jnp.where(valid, cell_of == jnp.arange(nc), True))),
          "every candidate index lies in its own (segment, lane) cell")
    check(bool(jnp.all(jnp.abs(v) >= cell_max * (1 - seg * 2.0 ** -23))),
          "every candidate is its cell's maximum (to key precision)")

    # --- Mosaic against the interpreter -----------------------------------
    for name, a, b in (("acc", acc, acc_i), ("values", vals, vals_i),
                       ("indices", idxs, idxs_i),
                       ("counts", counts, counts_i)):
        check(bool(jnp.array_equal(a, b)),
              f"mosaic == interpret: candidate {name}")

    # --- EF exactness through the wrapper the step calls ------------------
    r, t_new = jax.jit(lambda r_, g_, s_, t_: (
        gaussian_fused_ef_compress_batched(
            r_, g_, s_, k, t_, density=DENSITY, interpret=False)))(
                res, g, scale, t)
    comp = CompressedGrad(r.compressed.indices.reshape(-1),
                          r.compressed.values.reshape(-1))
    sent = int(jnp.sum(comp.values != 0))
    check(bool(jnp.array_equal(decompress(comp, n) + r.residual[0],
                               acc_ref[0])),
          f"decompress(comp) + residual == acc exactly ({sent} of {k} sent)")
    check(0.5 * k < sent <= k and float(t_new[0]) > 0,
          f"wrapper packed {sent} pairs, next threshold {float(t_new[0]):.4f}")


# -------------------------------------------------------------- trainer legs

def _records(run_dir: str) -> list:
    with open(os.path.join(run_dir, "metrics.jsonl")) as f:
        return [json.loads(line) for line in f]


def _argv(out: str, nworkers: int, max_steps: int, *extra: str) -> list:
    """The shipped config, shortened: without the two overrides a short run
    never leaves the dense program (``compress_warmup_steps: 390``) and
    never touches the kernel (``compressor: "gaussian"``)."""
    argv = ["--config", CONFIG, "--nworkers", str(nworkers),
            "--compressor", "auto",
            "--compress-warmup-steps", str(WARMUP_STEPS),
            "--max-steps", str(max_steps), "--log-every", str(LOG_EVERY),
            "--eval-max-batches", "4", "--save-every-epochs", "1",
            "--output-dir", out, *extra]
    say("train " + " ".join(argv))
    return argv


def trainer_leg(out: str, nworkers: int, max_steps: int) -> dict:
    t0 = time.perf_counter()
    trainer = train.make_trainer(_argv(out, nworkers, max_steps))
    t_construct = time.perf_counter() - t0
    try:
        ts, plan = trainer.ts, trainer.plan
        check(plan.total_numel == VGG16_PARAMS and len(plan.buckets) == 1,
              f"VGG-16 at full width: {plan.total_numel} parameters, one "
              f"bucket, k={plan.total_k}")
        check(trainer._comp.name == "gaussian_fused"
              and ts.kernel_mode == "mosaic"
              and ts.ef_numel == VGG16_EF_NUMEL,
              f"selector {trainer._comp.name}, kernel {ts.kernel_mode}, "
              f"ef_numel {ts.ef_numel} (the fused-EF path)")
        check(trainer.mesh.size == nworkers
              and len({d.id for d in trainer.mesh.devices.flat}) == nworkers,
              f"mesh of {nworkers} distinct device(s)")
        ef = trainer.state.ef_residual
        check(len(ef.addressable_shards) == nworkers
              and all(s.data.shape == (ts.ef_numel,)
                      for s in ef.addressable_shards),
              "fresh state: one EF row per worker, created in place")

        t0 = time.perf_counter()
        trainer.fit()
        t_fit = time.perf_counter() - t0
        check(trainer.step == max_steps, f"{max_steps} steps taken")

        recs = _records(trainer.run_dir)
        tr = [r for r in recs if r["event"] == "train"]
        dense = [r for r in tr if "wire_format" not in r]
        sparse = [r for r in tr if "wire_format" in r]
        check(len(tr) == max_steps // LOG_EVERY and len(dense) == 1
              and len(sparse) == len(tr) - 1,
              f"{len(dense)} dense and {len(sparse)} sparse log intervals")
        for r in tr:
            say(f"  step {r['step']:3d} loss={r['loss']:.4f} "
                f"step_s={r['step_s']:.4f} sel={r['num_selected']:.0f} "
                f"ef_norm={r['ef_norm']:.3e} bytes={r['bytes_sent']} "
                f"mfu={r.get('mfu')}")
        check(all(np.isfinite(r["loss"]) and r["skipped"] == 0
                  and r["nonfinite"] == 0 for r in tr),
              "every loss finite, no step skipped")
        check(all(r["ef_norm"] > 0 for r in sparse)
              and all(r["ef_norm"] == 0 for r in dense),
              "ef_norm > 0 on sparse intervals, 0 during dense warm-up")
        # the carried threshold starts cold and the controller moves it at
        # most x4 a step, gently (gain 0.18), while the young run's gradient
        # scale is still falling: two intervals to settle
        warmed = sparse[2:]
        check(all(0.5 * plan.total_k <= r["num_selected"]
                  <= 2 * plan.total_k for r in warmed),
              f"num_selected within [0.5, 2] x k={plan.total_k} once warm")
        per_entry = {wire.WIRE_LEGACY: 8, wire.WIRE_PACKED: 4}
        check(all(r["wire_format"] == ts.wire_format
                  and r["bytes_sent"]
                  == per_entry[ts.wire_format] * plan.total_k
                  for r in sparse)
              and all(r["bytes_sent"] == 4 * plan.total_numel
                      for r in dense),
              f"bytes_sent == {per_entry[ts.wire_format]} B x k "
              f"({ts.wire_format}) sparse, 4 B x n dense")
        check(all("mfu" in r for r in tr), "mfu on every logged record")
        check(any(r["event"] == "eval" and np.isfinite(r["val_loss"])
                  for r in recs), "an eval record with a finite loss")
        check(not any(r["event"] == "restore_fallback" for r in recs),
              "no restore_fallback event")
        ckpt = latest_checkpoint(trainer.ckpt_dir)
        check(ckpt is not None and ckpt.endswith(f"step_{max_steps:08d}"),
              f"sealed checkpoint {ckpt and os.path.basename(ckpt)}")

        batch = trainer._probe_batch
        lowered = ts.sparse_step.lower(trainer.state, batch)
        n_kernels = lowered.as_text().count("tpu_custom_call")
        check(n_kernels == 1,
              f"lowered sparse step holds {n_kernels} tpu_custom_call")
        if nworkers > 1:
            placement_checks(trainer, lowered, batch)
        return {"nworkers": nworkers,
                "construct_s": round(t_construct, 1),
                "fit_s": round(t_fit, 1),
                "steady_sparse_step_s": round(statistics.median(
                    r["step_s"] for r in warmed), 5),
                "ckpt_dir": trainer.ckpt_dir}
    finally:
        trainer.close()


def placement_checks(trainer, lowered_sparse, batch) -> None:
    """Where the state lives after training on several chips, and which
    collectives the compiled programs hold."""
    ts, st, p = trainer.ts, trainer.state, trainer.mesh.size
    devs = {d.id for d in trainer.mesh.devices.flat}
    shards = st.ef_residual.addressable_shards
    check(len(shards) == p and {s.device.id for s in shards} == devs
          and all(s.data.shape == (ts.ef_numel,) for s in shards),
          f"ef_residual: {p} shards of {ts.ef_numel} on {p} distinct chips")
    leaves = jax.tree_util.tree_leaves(st.params)
    check(all(x.sharding.is_fully_replicated
              and {d.id for d in x.sharding.device_set} == devs
              for x in leaves),
          f"all {len(leaves)} parameter leaves replicated on {p} chips")
    cs = st.comp_state
    check(len(cs.addressable_shards) == p
          and all(s.data.shape == (1, 1) for s in cs.addressable_shards),
          "comp_state sharded: one threshold row per worker")
    # The program asks for an all_gather of the k packed pairs; XLA's TPU
    # pipeline may serve a small one as an all-reduce of a P*k buffer. So:
    # the request in the lowered program, and in the compiled one whichever
    # collective carries the [P*k] payload across all P chips.
    n_asked = lowered_sparse.as_text().count("stablehlo.all_gather")
    check(n_asked >= 2, f"lowered sparse step asks for {n_asked} all_gathers")
    k, n = trainer.plan.total_k, trainer.plan.total_numel
    hlo_sparse = lowered_sparse.compile().as_text()
    hlo_dense = ts.dense_step.lower(st, batch).compile().as_text()
    for payload in (f"s32[{p * k}]", f"f32[{p * k}]"):
        op = _collective_over(hlo_sparse, payload, p)
        check(op is not None, f"compiled sparse step moves {payload} "
                              f"across {p} chips in an {op}")
    op = _collective_over(hlo_dense, f"f32[{n}]", p)
    check(op == "all-reduce", f"compiled dense step moves f32[{n}] across "
                              f"{p} chips in an {op}")
    for d in trainer.mesh.devices.flat:
        say(f"  device {d.id}: peak_bytes_in_use="
            f"{d.memory_stats()['peak_bytes_in_use'] / 2**20:.0f} MiB")


def _collective_over(hlo: str, payload: str, participants: int):
    """Name of the collective (sync or async-start form) in compiled HLO
    text that carries ``payload`` (e.g. ``f32[59948]``) with replica groups
    spanning ``participants`` devices — explicit ``{{0,1,2,3}}`` or iota
    ``[1,4]<=[4]`` notation — or None."""
    explicit = "{{" + ",".join(str(i) for i in range(participants)) + "}}"
    iota = f"[1,{participants}]<=[{participants}]"
    for line in hlo.splitlines():
        for op in ("all-gather", "all-reduce"):
            if ((f" {op}(" in line or f" {op}-start(" in line)
                    and payload in line
                    and (explicit in line or iota in line)):
                return op
    return None


def resume_leg(out: str, ckpt_dir: str, start: int, more: int) -> None:
    trainer = train.make_trainer(
        _argv(out, 1, start + more, "--resume", ckpt_dir))
    try:
        # "nothing to restore" is a cold start that exits 0 — so the step
        # is what proves the restore happened
        check(trainer.step == start, f"resumed at step {trainer.step}")
        check(float(jnp.linalg.norm(trainer.state.ef_residual)) > 0,
              "the restored EF residual is not a fresh one")
        trainer.fit()
        check(trainer.step == start + more, f"{more} more steps taken")
        recs = _records(trainer.run_dir)
        check(not any(r["event"] == "restore_fallback" for r in recs),
              "no restore_fallback event")
        check(all(np.isfinite(r["loss"]) and r["skipped"] == 0
                  for r in recs if r["event"] == "train"),
              "post-resume losses finite, no step skipped")
    finally:
        trainer.close()


def main() -> None:
    t_start = time.perf_counter()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    versions = {p: importlib.metadata.version(p)
                for p in ("jax", "jaxlib", "libtpu", "flax", "optax",
                          "orbax-checkpoint")}
    say(f"platform={device['platform']} device_kind={device['kind']!r} "
        f"device_count={device['count']}")
    say("versions " + " ".join(f"{k}={v}" for k, v in versions.items()))
    cache_dir = enable_compile_cache()
    files_before = _n_cache_files(cache_dir)
    say(f"compile cache {cache_dir}: {files_before} files")
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)

    os.makedirs(os.path.join(HERE, "runs"), exist_ok=True)
    out = tempfile.mkdtemp(prefix="chip_smoke_",
                           dir=os.path.join(HERE, "runs"))
    try:
        kernel_leg()
        one = trainer_leg(os.path.join(out, "one"), 1, 24)
        resume_leg(os.path.join(out, "resume"), one.pop("ckpt_dir"), 24, 4)
        four = None
        if device["count"] >= 4:
            four = trainer_leg(os.path.join(out, "four"), 4, 24)
            four.pop("ckpt_dir")
        else:
            say(f"four-chip leg not run: {device['count']} device(s)")
    finally:
        shutil.rmtree(out, ignore_errors=True)

    spent = sum(compile_secs.values())
    saved = cache_events.pop("compile_s_saved", 0.0)
    setup = {
        "total_s": round(time.perf_counter() - t_start, 1),
        "one_chip": one, "four_chip": four,
        "compile_s_by_program": {k: round(v, 1) for k, v
                                 in sorted(compile_secs.items()) if v >= 1},
        "compile_s_spent": round(spent, 1),
        "compile_s_saved_by_cache": round(saved, 1),
        "cache": {"dir": cache_dir, "files_before": files_before,
                  "files_after": _n_cache_files(cache_dir), **cache_events},
        # warm: the cache saved more compile time than this run spent
        "warm_start": saved > spent,
    }
    say("set-up and sanity seconds (not benchmark metrics): "
        + json.dumps(setup))
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
