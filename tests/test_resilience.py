"""Fault-tolerance tests, driven end-to-end by the chaos harness
(training/chaos.py) on the virtual 8-device CPU mesh.

Covers every layer of the failure model in docs/RESILIENCE.md:
the in-step non-finite guard (bit-identical no-op, EF residual included),
the host-side monitor (skip budget, loss spikes, rollback accounting),
sealed checkpoints (commit manifest, tmp/truncated-dir exclusion,
corrupt-fallback restore), graceful preemption (checkpoint-then-exit and
resume), prefetch retry with bounded backoff, and the ISSUE acceptance
scenario: a chaos run (NaN step + corrupted latest checkpoint) that rolls
back and still lands near the uninjected run's final loss.
"""

import json
import os
import signal

import numpy as np
import pytest

import jax

from gaussiank_sgd_tpu import data as data_lib
from gaussiank_sgd_tpu.training import chaos
from gaussiank_sgd_tpu.training.checkpoint import (
    MANIFEST, gc_checkpoints, is_committed, latest_checkpoint,
    list_checkpoints, restore_latest_good)
from gaussiank_sgd_tpu.training.config import TrainConfig
from gaussiank_sgd_tpu.training.resilience import (
    GracefulShutdown, ResilienceMonitor, ResiliencePolicy, TrainingPreempted)
from gaussiank_sgd_tpu.training.trainer import Trainer


def make_cfg(tmp_path, **kw):
    base = dict(
        dnn="mnistnet", dataset="mnist", batch_size=8, nworkers=8,
        lr=0.05, momentum=0.9, weight_decay=0.0, epochs=1, max_steps=12,
        compressor="gaussian", density=0.01, compress_warmup_steps=4,
        warmup_epochs=0.0, compute_dtype="float32", output_dir=str(tmp_path),
        log_every=5, eval_every_epochs=0, save_every_epochs=0, seed=0,
    )
    base.update(kw)
    return TrainConfig(**base)


def read_events(t, kind=None):
    recs = [json.loads(line) for line in
            open(os.path.join(t.run_dir, "metrics.jsonl"))]
    return [r for r in recs if kind is None or r.get("event") == kind]


def snapshot(state):
    """Host copies of everything the guard must freeze on a skipped step."""
    return [np.asarray(jax.device_get(x)) for x in jax.tree_util.tree_leaves(
        (state.params, state.model_state, state.opt_state,
         state.ef_residual))]


# ---------------------------------------------------------------------------
# in-step guard: a non-finite step is a bit-identical no-op
# ---------------------------------------------------------------------------

def test_guard_skips_are_bit_identical_noops(tmp_path):
    """NaN batches at a dense-warmup step AND a sparse step: params,
    model_state, opt_state, and the EF residual are bit-identical to the
    pre-step state (EF is the critical one: a NaN entering error feedback
    is re-sent forever), while the step counter still advances."""
    t = Trainer(make_cfg(tmp_path, compress_warmup_steps=3, max_steps=8,
                         log_every=1))
    fired = chaos.inject_nan_batches(t, {1, 5})   # dense step 1, sparse 5
    t.train(1)                                    # step 0: clean
    before_dense = snapshot(t.state)
    rec = t.train(1)                              # step 1: poisoned (dense)
    assert rec["skipped"] == 1.0 and rec["nonfinite"] > 0
    after_dense = snapshot(t.state)
    for a, b in zip(before_dense, after_dense):
        np.testing.assert_array_equal(a, b)
    assert t.step == 2                            # counter still advanced

    t.train(3)                                    # steps 2-4: clean
    before_sparse = snapshot(t.state)
    ef_before = np.asarray(jax.device_get(t.state.ef_residual))
    rec = t.train(1)                              # step 5: poisoned (sparse)
    assert rec["skipped"] == 1.0
    for a, b in zip(before_sparse, snapshot(t.state)):
        np.testing.assert_array_equal(a, b)
    # the EF-residual invariant, stated on its own: bit-identical
    ef_after = np.asarray(jax.device_get(t.state.ef_residual))
    assert np.array_equal(ef_before, ef_after)
    assert np.all(np.isfinite(ef_after))

    rec = t.train(1)                              # step 6: clean again
    assert rec["skipped"] == 0.0
    changed = any(not np.array_equal(a, b) for a, b in
                  zip(before_sparse, snapshot(t.state)))
    assert changed, "clean step after a skip must update state"
    assert fired == {1, 5}
    skips = read_events(t, "skip")
    assert [r["step"] for r in skips] == [2, 6]   # 1-based completed steps
    assert all(r["nonfinite"] > 0 for r in skips)
    t.close()


def test_guard_skip_advances_optax_schedule_count(tmp_path):
    """REVIEW fix: on the optax path (nesterov forces it off flat_opt) a
    guard-skipped step must still advance the integer schedule counters
    in opt_state — otherwise the optax LR schedule lags state.step by one
    per skip — while the float momentum buffers stay bit-identical."""
    t = Trainer(make_cfg(tmp_path, nesterov=True, max_steps=4, log_every=1,
                         compress_warmup_steps=2))
    chaos.inject_nan_batches(t, {1})
    t.train(1)                                    # step 1: clean
    leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(
        jax.device_get(t.state.opt_state))]
    ints_before = [x for x in leaves if np.issubdtype(x.dtype, np.integer)]
    floats_before = [x for x in leaves
                     if not np.issubdtype(x.dtype, np.integer)]
    assert ints_before, "optax sgd(schedule) must carry a step counter"
    assert all(int(c) == 1 for c in ints_before)
    rec = t.train(1)                              # step 2: skipped
    assert rec["skipped"] == 1.0
    leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(
        jax.device_get(t.state.opt_state))]
    ints_after = [x for x in leaves if np.issubdtype(x.dtype, np.integer)]
    floats_after = [x for x in leaves
                    if not np.issubdtype(x.dtype, np.integer)]
    assert all(int(c) == 2 for c in ints_after)   # aligned with state.step
    assert t.step == 2
    for a, b in zip(floats_before, floats_after):
        np.testing.assert_array_equal(a, b)       # momentum untouched
    t.close()


def test_poison_batch_requires_float_leaf():
    with pytest.raises(ValueError, match="no float leaf"):
        chaos.poison_batch((np.arange(4), np.arange(4)))
    x, y = chaos.poison_batch((np.ones((2, 2), np.float32), np.arange(2)))
    assert np.all(np.isnan(x)) and np.array_equal(y, np.arange(2))


# ---------------------------------------------------------------------------
# host-side monitor (pure-Python unit tests)
# ---------------------------------------------------------------------------

def test_monitor_skip_budget_and_reset():
    m = ResilienceMonitor(ResiliencePolicy(max_consecutive_skips=3))
    for s in range(2):
        m.observe(s, float("nan"), skipped=1.0)
    assert m.should_rollback() is None
    m.observe(2, float("nan"), skipped=1.0)
    assert m.should_rollback() == "skip_budget"
    assert m.pending_since == 2      # step of the budget-tripping skip
    assert m.note_rollback() == 1
    assert m.should_rollback() is None and m.consecutive_skips == 0
    assert m.pending_since is None
    assert m.lr_scale == 0.5
    # a clean step between skips resets the streak
    m.observe(3, 1.0, skipped=1.0)
    m.observe(4, 1.0, skipped=0.0)
    m.observe(5, 1.0, skipped=1.0)
    assert m.consecutive_skips == 1 and m.should_rollback() is None


def test_monitor_loss_spike():
    m = ResilienceMonitor(ResiliencePolicy(
        max_consecutive_skips=0, loss_spike_factor=2.0, loss_ema_beta=0.5,
        loss_ema_warmup=2))
    m.observe(0, 1.0, 0.0)
    m.observe(1, 1.0, 0.0)
    m.observe(2, 1.1, 0.0)          # warmed up, no spike
    assert m.should_rollback() is None
    ema_before = m._loss_ema
    m.observe(3, 10.0, 0.0)         # 10 > 2 * ema
    assert m.should_rollback() == "loss_spike"
    assert m.pending_since == 3
    assert m._loss_ema == ema_before   # spike excluded from the EMA
    # non-finite loss on an UNSKIPPED step (guard off) also counts
    m.note_rollback()
    m.observe(4, float("inf"), 0.0)
    assert m.should_rollback() == "loss_spike"


def test_monitor_rollback_budget_exhausts_loudly():
    m = ResilienceMonitor(ResiliencePolicy(max_rollbacks=1))
    assert m.note_rollback() == 1
    with pytest.raises(RuntimeError, match="rollback budget exhausted"):
        m.note_rollback()


def test_policy_active_flags():
    assert not ResiliencePolicy(max_consecutive_skips=0,
                                loss_spike_factor=0.0).active
    assert ResiliencePolicy(max_consecutive_skips=1,
                            loss_spike_factor=0.0).active
    assert ResiliencePolicy(max_consecutive_skips=0,
                            loss_spike_factor=3.0).active


def test_graceful_shutdown_real_signal():
    gs = GracefulShutdown().install()
    try:
        assert not gs.requested
        os.kill(os.getpid(), signal.SIGTERM)
        assert gs.requested
    finally:
        gs.uninstall()


# ---------------------------------------------------------------------------
# sealed checkpoints: commit manifest, exclusion, corrupt-fallback, GC
# ---------------------------------------------------------------------------

def test_checkpoint_sealing_corruption_and_fallback(tmp_path):
    t = Trainer(make_cfg(tmp_path, max_steps=12, log_every=50))
    t.train(2)
    p2 = t._save_checkpoint()
    t.train(2)
    p4 = t._save_checkpoint()
    t.train(2)
    p6 = t._save_checkpoint()
    assert all(is_committed(p) for p in (p2, p4, p6))
    assert latest_checkpoint(t.ckpt_dir) == p6

    # an in-flight orbax tmp dir is never a candidate
    fake_tmp = os.path.join(
        t.ckpt_dir, "step_00000099.orbax-checkpoint-tmp-1234")
    os.makedirs(fake_tmp)
    assert latest_checkpoint(t.ckpt_dir) == p6

    # unsealed == aborted-before-commit: excluded from the listing
    chaos.corrupt_checkpoint(p6, "unseal")
    assert latest_checkpoint(t.ckpt_dir) == p4
    # truncation: still sealed, but the manifest inventory catches it
    chaos.corrupt_checkpoint(p4, "truncate")
    assert latest_checkpoint(t.ckpt_dir) == p2
    assert [s for s, _ in list_checkpoints(t.ckpt_dir)] == [2]

    # garbage at the right sizes: sealed AND inventory-valid, so only the
    # restore attempt itself can catch it -> fall back to the previous one
    t.train(2)
    p8 = t._save_checkpoint()
    chaos.corrupt_checkpoint(p8, "garbage")
    assert latest_checkpoint(t.ckpt_dir) == p8      # looks fine on disk
    skipped = []
    state, path = restore_latest_good(
        t.ckpt_dir, t.state, t.mesh,
        on_skip=lambda p, e: skipped.append(p))
    assert path == p2 and skipped == [p8]
    assert int(jax.device_get(state.step)) == 2

    # external state assignment drops the cached data iterator + step cache
    # (the stream must realign to the restored step)
    assert t._train_iter() is not None
    t.state = state
    assert t._iter is None and not hasattr(t, "_step_cache")
    assert t.step == 2
    t.train(1)
    assert t.step == 3

    # keep-last-k GC removes only sealed checkpoints, oldest first; the
    # newest SEALED one kept is garbage-p8, so a restore over what's left
    # exhausts every candidate and fails loud (not FileNotFoundError —
    # sealed candidates existed, they just don't restore)
    removed = gc_checkpoints(t.ckpt_dir, keep_last=1)
    assert removed == [p2] and not os.path.exists(p2)
    assert os.path.exists(p6)       # unsealed debris is left alone
    with pytest.raises(RuntimeError,
                       match="every committed checkpoint failed"):
        restore_latest_good(t.ckpt_dir, t.state, t.mesh)
    assert gc_checkpoints(t.ckpt_dir, keep_last=0) == []   # retention off
    # and with the garbage one gone too: nothing sealed at all
    chaos.corrupt_checkpoint(p8, "unseal")
    with pytest.raises(FileNotFoundError):
        restore_latest_good(t.ckpt_dir, t.state, t.mesh)
    t.close()


def test_corrupt_checkpoint_rejects_unknown_mode(tmp_path):
    with pytest.raises(ValueError, match="unknown corruption mode"):
        chaos.corrupt_checkpoint(str(tmp_path), "melt")


# ---------------------------------------------------------------------------
# rollback paths
# ---------------------------------------------------------------------------

def test_rollback_without_checkpoint_fails_loud(tmp_path):
    t = Trainer(make_cfg(tmp_path, max_steps=8, log_every=1,
                         max_consecutive_skips=1, save_every_steps=0))
    chaos.inject_nan_batches(t, {1})
    with pytest.raises(RuntimeError, match="no restorable checkpoint"):
        t.train(4)
    t.close()


def test_chaos_e2e_rollback_matches_clean_run(tmp_path):
    """ISSUE acceptance: NaN at one step + garbage-corrupted latest
    checkpoint. The run skips the step, trips the skip budget, falls back
    past the corrupt checkpoint to an older good one, backs off the LR,
    replays, and finishes all 16 steps with a final loss near the
    uninjected run's (same seed, same data order)."""
    # lr low enough that the CLEAN trajectory is stable: the comparison
    # must measure recovery fidelity, not the (lr-halving) rollback
    # accidentally beating an lr too hot for the baseline
    base = Trainer(make_cfg(tmp_path / "base", max_steps=16, log_every=2,
                            lr=0.01))
    base.fit()
    base_final = read_events(base, "train")[-1]["loss"]
    base.close()

    t = Trainer(make_cfg(tmp_path / "chaos", max_steps=16, log_every=2,
                         lr=0.01, save_every_steps=4,
                         max_consecutive_skips=1))
    t.train(8)                       # sealed checkpoints at steps 4 and 8
    p8 = latest_checkpoint(t.ckpt_dir)
    assert p8.endswith("step_00000008")
    chaos.corrupt_checkpoint(p8, "garbage")
    fired = chaos.inject_nan_batches(t, {8})   # poisons the batch -> step 9
    while t.step < t.total_steps:
        t.train(t.total_steps - t.step)
    assert t.step == 16 and fired == {8}

    skips = read_events(t, "skip")
    assert [r["step"] for r in skips] == [9]
    rollbacks = read_events(t, "rollback")
    assert len(rollbacks) == 1
    rb = rollbacks[0]
    assert rb["reason"] == "skip_budget" and rb["to_step"] == 4
    assert rb["lr_scale"] == 0.5 and rb["checkpoint"].endswith(
        "step_00000004")
    fallbacks = read_events(t, "restore_fallback")
    assert [r["checkpoint"] for r in fallbacks] == [p8]

    chaos_final = read_events(t, "train")[-1]["loss"]
    assert np.isfinite(chaos_final)
    assert abs(chaos_final - base_final) <= 0.5 * abs(base_final), (
        f"chaos run diverged: {chaos_final} vs clean {base_final}")
    # post-rollback EF residual stayed finite through the whole episode
    assert np.all(np.isfinite(np.asarray(jax.device_get(
        t.state.ef_residual))))
    t.close()


def test_spike_rollback_excludes_post_spike_checkpoint(tmp_path):
    """REVIEW fix: when a cadence save lands in the same interval the loss
    spike is detected, the diverged state must NOT be sealed and become
    its own rollback target — the save is suppressed while a rollback is
    pending, and the restore excludes checkpoints at/after the anomaly
    step, so the run rewinds to the last PRE-spike checkpoint."""
    t = Trainer(make_cfg(tmp_path, max_steps=12, log_every=2, lr=0.01,
                         save_every_steps=2, loss_spike_factor=1.5))
    # large-but-finite fill: the loss spikes without tripping the
    # non-finite guard, so the divergence actually enters the params
    chaos.inject_nan_batches(t, {6}, fill=100.0)  # poisons step 7
    while t.step < t.total_steps:
        t.train(t.total_steps - t.step)
    assert t.step == 12
    rollbacks = read_events(t, "rollback")
    assert len(rollbacks) == 1
    rb = rollbacks[0]
    assert rb["reason"] == "loss_spike"
    # pre-fix this restored the just-sealed step-8 checkpoint (diverged);
    # now step 6 — the newest checkpoint older than the observed spike
    assert rb["to_step"] == 6
    assert rb["checkpoint"].endswith("step_00000006")
    assert rb["lr_scale"] == 0.5
    final = read_events(t, "train")[-1]["loss"]
    assert np.isfinite(final)
    t.close()


def test_resume_from_older_step_overwrites_stale_checkpoints(tmp_path):
    """REVIEW fix: after an explicit resume from an OLDER checkpoint, the
    new trajectory re-reaches steps the old one already sealed — those
    saves must overwrite the stale dirs (sealed-idempotency used to
    silently no-op them), while same-step re-saves within one trajectory
    stay idempotent."""
    t = Trainer(make_cfg(tmp_path, max_steps=6, save_every_steps=2,
                         log_every=50))
    t.train(6)                        # seals steps 2, 4, 6
    t.close()
    p2 = os.path.join(t.ckpt_dir, "step_00000002")
    p4 = os.path.join(t.ckpt_dir, "step_00000004")
    assert is_committed(p2) and is_committed(p4)
    stale = json.load(open(os.path.join(p4, MANIFEST)))

    # a different-lr run resumed from step 2 is a different trajectory
    t2 = Trainer(make_cfg(tmp_path, max_steps=6, save_every_steps=2,
                          log_every=50, lr=0.02, resume=p2))
    assert t2.step == 2
    t2.train(2)                       # re-reaches step 4 -> must rewrite
    fresh = json.load(open(os.path.join(p4, MANIFEST)))
    assert fresh["wrote_unix"] > stale["wrote_unix"]
    assert is_committed(p4)
    # idempotency within the new trajectory is preserved: saving step 4
    # again does not rewrite the sealed dir
    t2._save_checkpoint()
    again = json.load(open(os.path.join(p4, MANIFEST)))
    assert again["wrote_unix"] == fresh["wrote_unix"]
    t2.close()


# ---------------------------------------------------------------------------
# preemption: checkpoint at the next step boundary, then clean exit + resume
# ---------------------------------------------------------------------------

def test_preemption_checkpoints_and_resumes(tmp_path):
    cfg = make_cfg(tmp_path, max_steps=10, log_every=2)
    t = Trainer(cfg)
    t.train(3)
    t.shutdown.request()             # programmatic SIGTERM equivalent
    result = t.fit()                 # honors the request at the boundary
    assert result.get("preempted_at") == 4.0
    pre = read_events(t, "preempt")
    assert len(pre) == 1 and pre[0]["step"] == 4
    ckpt = latest_checkpoint(t.ckpt_dir)
    assert ckpt is not None and is_committed(ckpt)
    assert ckpt.endswith("step_00000004")
    t.close()

    # a rescheduled run resumes from the sealed preemption checkpoint and
    # finishes the remaining steps
    t2 = Trainer(make_cfg(tmp_path, max_steps=10, log_every=2,
                          resume=t.ckpt_dir))
    assert t2.step == 4
    t2.fit()
    assert t2.step == 10
    t2.close()


def test_train_raises_training_preempted(tmp_path):
    t = Trainer(make_cfg(tmp_path, max_steps=8))
    t.shutdown.request()
    with pytest.raises(TrainingPreempted) as ei:
        t.train(4)
    assert ei.value.step == 1        # first step boundary after the request
    assert is_committed(ei.value.ckpt_path)
    t.close()


# ---------------------------------------------------------------------------
# data-loader retry with bounded backoff
# ---------------------------------------------------------------------------

def test_prefetch_retries_transient_io_errors():
    items = list(range(6))
    flaky = chaos.FlakyIterator(iter(items), fail_pulls=[1, 4],
                                failures_per_pull=2)
    events = []
    out = list(data_lib.prefetch(flaky, depth=2, max_retries=3,
                                 backoff_s=0.001, on_event=events.append))
    assert out == items              # nothing lost, order preserved
    assert flaky.raised == 4
    assert [e["event"] for e in events] == ["io_retry"] * 4
    assert [e["attempt"] for e in events] == [1, 2, 1, 2]
    assert all(e["max_retries"] == 3 for e in events)
    assert all(e["backoff_s"] > 0 for e in events)


def test_prefetch_retry_exhaustion_propagates():
    flaky = chaos.FlakyIterator(iter(range(3)), fail_pulls=[0],
                                failures_per_pull=10)
    gen = data_lib.prefetch(flaky, depth=1, max_retries=2, backoff_s=0.001)
    with pytest.raises(RuntimeError, match="prefetch thread failed") as ei:
        list(gen)
    assert isinstance(ei.value.__cause__, chaos.TransientIOError)
    assert flaky.raised == 3         # initial + 2 retries


def test_prefetch_zero_retries_is_passthrough():
    flaky = chaos.FlakyIterator(iter(range(3)), fail_pulls=[1])
    with pytest.raises(RuntimeError, match="prefetch thread failed"):
        list(data_lib.prefetch(flaky, depth=1))


def test_prefetch_generator_source_error_not_swallowed():
    """REVIEW fix: a transient error finalizes a GENERATOR source, so the
    retry's next() hits StopIteration — which used to read as a clean
    end-of-stream, silently truncating an infinite stream. The original
    error must surface as the prefetch failure cause instead."""
    def gen():
        yield 0
        yield 1
        raise chaos.TransientIOError("disk vanished")

    out = []
    it = data_lib.prefetch(gen(), depth=1, max_retries=3, backoff_s=0.001)
    with pytest.raises(RuntimeError, match="prefetch thread failed") as ei:
        for x in it:
            out.append(x)
    assert out == [0, 1]             # nothing yielded past the fault
    assert isinstance(ei.value.__cause__, chaos.TransientIOError)


def test_epoch_stream_matches_generator_and_resumes():
    """data_lib.EpochStream == the epoch-looping generator it replaces
    (same batches at every resume offset), and it survives a mid-epoch
    transient error: the retried pull returns the exact batch the clean
    stream would have."""
    ds = data_lib.ArrayDataset([np.arange(20, dtype=np.float32)],
                               batch_size=4, seed=0)   # 5 steps/epoch

    def ref_stream(start):
        ep, skip = start // 5, start % 5
        while True:
            for i, b in enumerate(ds.epoch(epoch_seed=7 + ep)):
                if skip and i < skip:
                    continue
                yield b
            skip = 0
            ep += 1

    for start in (0, 3, 7):
        s = data_lib.EpochStream(ds, 7, start)
        ref = ref_stream(start)
        for _ in range(12):          # crosses epoch boundaries
            np.testing.assert_array_equal(next(s)[0], next(ref)[0])

    flaky = chaos.FlakyEpochSource(ds, fail_batches=[2], times=1)
    s = data_lib.EpochStream(flaky, 7, 0)
    ref = ref_stream(0)
    for _ in range(8):
        while True:
            try:
                batch = next(s)
                break
            except chaos.TransientIOError:
                continue             # the retrying consumer's move
        np.testing.assert_array_equal(batch[0], next(ref)[0])
    assert flaky.raised == 1


def test_trainer_stream_survives_transient_io(tmp_path):
    """REVIEW fix, production path: a TransientIOError raised by the
    dataset inside the Trainer's own prefetch stream is retried (the
    stream is a resumable EpochStream, not a generator) — training
    finishes every step with io_retry events on record and the exact
    trajectory of an unfaulted run, instead of the stream silently
    ending."""
    base = Trainer(make_cfg(tmp_path / "base", max_steps=6, log_every=50))
    base_rec = base.train(6)
    base.close()

    t = Trainer(make_cfg(tmp_path / "flaky", max_steps=6, log_every=50,
                         io_backoff_s=0.001))
    flaky = chaos.FlakyEpochSource(t.train_ds, fail_batches=[2], times=2)
    t.train_ds = flaky
    rec = t.train(6)
    assert t.step == 6
    assert flaky.raised == 2
    retries = read_events(t, "io_retry")
    assert [r["attempt"] for r in retries] == [1, 2]
    assert all(r["max_retries"] == 3 for r in retries)
    # same batches in the same order -> identical final loss
    assert rec["loss"] == pytest.approx(base_rec["loss"], rel=1e-6)
    t.close()


def test_trainer_stream_retry_exhaustion_fails_loud(tmp_path):
    """A persistent loader fault exhausts io_retries and kills the run
    with the ORIGINAL error as the cause — pre-fix this surfaced as a
    bare StopIteration (the stream just ended)."""
    t = Trainer(make_cfg(tmp_path, max_steps=6, io_backoff_s=0.001))
    flaky = chaos.FlakyEpochSource(t.train_ds, fail_batches=[1], times=10)
    t.train_ds = flaky
    with pytest.raises(RuntimeError, match="prefetch thread failed") as ei:
        t.train(4)
    assert isinstance(ei.value.__cause__, chaos.TransientIOError)
    assert flaky.raised == 4         # initial + io_retries (3)
    t.close()


# ---------------------------------------------------------------------------
# the loop keeps one step in flight (PR 28): where the host acts on the
# finished state, it acts on the state of THAT step
# ---------------------------------------------------------------------------

def _params_bytes(state):
    return [np.asarray(jax.device_get(x)).tobytes()
            for x in jax.tree_util.tree_leaves(
                (state.params, state.opt_state, state.ef_residual))]


def _after(tmp_path, steps, **kw):
    """The state after `steps` steps of an undisturbed run."""
    ref = Trainer(make_cfg(tmp_path / f"ref{steps}", max_steps=12, **kw))
    ref.train(steps)
    got = _params_bytes(ref.state)
    ref.close()
    return got


def test_cadence_save_holds_the_state_after_its_own_step(tmp_path):
    """`train(7)` with a save every 3 steps: the checkpoints of steps 3 and
    6 hold the state after those steps and no later one (the loop does not
    dispatch step 4 before step 3 is sealed; the state is donated)."""
    from gaussiank_sgd_tpu.training.checkpoint import restore_checkpoint
    t = Trainer(make_cfg(tmp_path, max_steps=12, save_every_steps=3))
    t.train(7)
    assert t.step == 7
    saved = [r["step"] for r in read_events(t, "checkpoint")]
    assert saved == [3, 6]
    for step, path in list_checkpoints(t.ckpt_dir):
        state = restore_checkpoint(path, t.state, t.mesh,
                                   padded_numel=t.ts.ef_numel)
        assert int(jax.device_get(state.step)) == step
        assert _params_bytes(state) == _after(tmp_path, step)
    t.close()


def test_shutdown_with_a_step_in_flight_seals_at_that_steps_boundary(
        tmp_path):
    """The request arrives while batch 3 is pulled, with step 2 dispatched
    and not yet waited for. The loop dispatches step 3, reports step 2,
    waits for step 3, seals it and raises with it: 3 pulls, no step 4."""
    t = Trainer(make_cfg(tmp_path, max_steps=12))
    stream = t._train_iter()

    class Feed:
        pulled = 0

        def __iter__(self):
            return self

        def __next__(self):
            self.pulled += 1
            if self.pulled == 3:
                assert t._flight is not None and t._flight.done == 2
                t.shutdown.request()
            return next(stream)

    feed = Feed()
    with pytest.raises(TrainingPreempted) as ei:
        t.train(8, data_iter=feed)
    assert ei.value.step == 3 and feed.pulled == 3
    assert ei.value.ckpt_path.endswith("step_00000003")
    assert is_committed(ei.value.ckpt_path)
    assert [r["step"] for r in read_events(t, "preempt")] == [3]
    assert t.step == 3 and t._flight is None
    assert _params_bytes(t.state) == _after(tmp_path, 3)
    t.close()


def test_rollback_at_a_log_step_drops_the_step_in_flight(tmp_path):
    """The poisoned step 9 is skipped under its own number while step 10
    is in flight; the skip budget trips, and the rollback at log step 10,
    with step 11 dispatched, restores step 8's checkpoint over it. The
    dropped step is never reported; the call's other steps replay from the
    restored one: 6 dispatches = 9, 10, 11 (dropped), 9, 10, 11."""
    t = Trainer(make_cfg(tmp_path, max_steps=40, log_every=2, lr=0.01,
                         save_every_steps=4, max_consecutive_skips=1))
    t.train(8)
    fired = chaos.inject_nan_batches(t, {8})    # the batch of step 9
    t.train(6)
    assert fired == {8}
    assert [r["step"] for r in read_events(t, "skip")] == [9]
    rb = read_events(t, "rollback")
    assert len(rb) == 1 and rb[0]["to_step"] == 8
    assert rb[0]["reason"] == "skip_budget"
    assert t.step == 11 and t._flight is None
    steps = [r["step"] for r in read_events(t, "train")]
    assert steps == [2, 4, 6, 8, 10, 10]
    recs = read_events(t, "train")
    assert recs[-2]["skipped"] == 0.0 and recs[-2]["lr_scale"] == 1.0
    assert recs[-1]["lr_scale"] == 0.5
    t.close()
