"""Trainer integration tests — the end-to-end slice of SURVEY.md §7 stage 4,
on the virtual 8-device CPU mesh. Covers BASELINE config-1-shaped smoke
(dense resnet20/cifar10) and a compressed multi-worker run, checkpoints,
resume, eval metrics, and the PTB LM path."""

import glob
import json
import os

import numpy as np
import pytest

from gaussiank_sgd_tpu.training.config import TrainConfig
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


def test_trainer_end_to_end_compressed(tmp_path):
    t = Trainer(make_cfg(tmp_path))
    t.train(12)
    assert t.step == 12
    res = t.test()
    assert 0.0 <= res["top1"] <= 1.0
    assert res["val_loss"] > 0
    # metrics JSONL exists and has train records
    recs = [json.loads(l) for l in open(
        os.path.join(t.run_dir, "metrics.jsonl"))]
    assert any(r.get("event") == "train" for r in recs)
    assert any(r.get("event") == "config" for r in recs)
    tr = [r for r in recs if r.get("event") == "train"]
    # compressed steps send far fewer bytes than a dense exchange would
    n_params = next(r for r in recs if r.get("event") == "config")["n_params"]
    assert tr[-1]["bytes_sent"] < 0.05 * 4 * n_params
    t.close()


def test_trainer_dense_smoke_config1(tmp_path):
    """BASELINE config 1 shape: resnet20/cifar10, dense, 1 worker."""
    t = Trainer(make_cfg(tmp_path, dnn="resnet20", dataset="cifar10",
                         nworkers=1, compressor="none", batch_size=32,
                         max_steps=6, log_every=3))
    first = t.train(3)
    last = t.train(3)
    assert last["loss"] < first["loss"] * 1.5  # moving, not exploding
    t.close()


def test_trainer_loss_decreases_over_epoch(tmp_path):
    # note: lr is Goyal-scaled by nworkers (8x) inside the schedule
    t = Trainer(make_cfg(tmp_path, max_steps=24, compress_warmup_steps=5,
                         lr=0.01))
    t.train(24)
    recs = [json.loads(l) for l in open(
        os.path.join(t.run_dir, "metrics.jsonl"))]
    tr = [r for r in recs if r.get("event") == "train"]
    assert tr[-1]["loss"] < tr[0]["loss"]
    t.close()


def test_checkpoint_save_restore_roundtrip(tmp_path):
    from gaussiank_sgd_tpu.training.checkpoint import (latest_checkpoint,
                                                       restore_checkpoint,
                                                       save_checkpoint)
    import jax
    t = Trainer(make_cfg(tmp_path, max_steps=8))
    t.train(8)
    ckpt_dir = os.path.join(t.run_dir, "ckpt")
    save_checkpoint(ckpt_dir, t.state)
    path = latest_checkpoint(ckpt_dir)
    assert path and path.endswith("step_00000008")

    t2 = Trainer(make_cfg(tmp_path, max_steps=8, run_id="run2"))
    restored = restore_checkpoint(path, t2.state, t2.mesh)
    assert int(restored.step) == 8
    # params AND the sharded EF residual round-trip exactly
    f1 = jax.tree_util.tree_leaves(t.state.params)
    f2 = jax.tree_util.tree_leaves(restored.params)
    for a, b in zip(f1, f2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(t.state.ef_residual),
                                  np.asarray(restored.ef_residual))
    assert restored.ef_residual.ndim == 1  # live layout is flat [P*N]
    # restored state must come back with live shardings: stepping it must
    # work (catches restores committed to a single device)
    t2.state = restored
    t2.train(1)
    assert t2.step == 9
    t.close(); t2.close()


def test_trainer_resume_from_config(tmp_path):
    t = Trainer(make_cfg(tmp_path, max_steps=8))
    t.train(8)
    from gaussiank_sgd_tpu.training.checkpoint import save_checkpoint
    save_checkpoint(os.path.join(t.run_dir, "ckpt"), t.state)
    t.close()

    t2 = Trainer(make_cfg(tmp_path, max_steps=8,
                          resume=os.path.join(t.run_dir, "ckpt")))
    assert t2.step == 8
    t2.close()


def test_trainer_ptb_lstm(tmp_path):
    # toy LSTM: this test exercises the LM plumbing (bptt batching, CE per
    # token, perplexity eval, clipping), not model capacity — keep it small
    # so the full suite fits a CI window (VERDICT r1 weak #2)
    t = Trainer(make_cfg(tmp_path, dnn="lstm", dataset="ptb", batch_size=2,
                         nworkers=8, clip_norm=0.25, compressor="gaussian",
                         density=0.01, max_steps=4, compress_warmup_steps=2,
                         model_kwargs=dict(embed_dim=32, hidden_dim=32),
                         dataset_kwargs=dict(vocab_size=256, bptt=16,
                                             synthetic_tokens_n=8192),
                         eval_max_batches=4))
    t.train(4)
    res = t.test()
    assert res["perplexity"] > 1.0
    t.close()


def test_trainer_transformer_wmt(tmp_path):
    """BASELINE config 5 shape (toy): seq2seq transformer on the synthetic
    copy-reverse WMT stand-in with RandomK-EC compression."""
    t = Trainer(make_cfg(tmp_path, dnn="transformer", dataset="wmt",
                         batch_size=2, nworkers=8, compressor="randomkec",
                         density=0.01, max_steps=4, compress_warmup_steps=2,
                         clip_norm=1.0, label_smoothing=0.1,
                         model_kwargs=dict(dim=32, heads=2, enc_layers=1,
                                           dec_layers=1, ffn=64, dropout=0.0,
                                           max_len=32, seq_len=16),
                         dataset_kwargs=dict(vocab_size=64, src_len=16,
                                             tgt_len=16,
                                             synthetic_examples=128),
                         eval_max_batches=2))
    t.train(4)
    res = t.test()
    assert np.isfinite(res["val_loss"]) and 0.0 <= res["top1"] <= 1.0
    t.close()


def test_trainer_hierarchical_mesh(tmp_path):
    """ici x dcn hierarchical DP through the full Trainer: the sparse
    allgather rides the ici axis, dense partials psum over dcn."""
    t = Trainer(make_cfg(tmp_path, nworkers=0, ici_size=4, dcn_size=2,
                         max_steps=6, compress_warmup_steps=2))
    assert tuple(t.mesh.axis_names) == ("dcn_dp", "ici_dp")
    assert t.nworkers == 8
    t.train(6)
    res = t.test()
    assert 0.0 <= res["top1"] <= 1.0
    t.close()


def test_trainer_warmup_switches_to_sparse(tmp_path):
    t = Trainer(make_cfg(tmp_path, max_steps=8, compress_warmup_steps=4,
                         log_every=1))
    t.train(8)
    recs = [json.loads(l) for l in open(
        os.path.join(t.run_dir, "metrics.jsonl"))]
    tr = {r["step"]: r for r in recs if r.get("event") == "train"}
    # steps 1..4 are dense warm-up (full byte volume), steps 5..8 sparse;
    # at density 0.01 the sparse payload is k*(4B idx + 4B val) = 2% of
    # params -> dense/sparse byte ratio = 50x
    assert tr[4]["bytes_sent"] > 20 * tr[8]["bytes_sent"]
    t.close()


# ---------------------------------------------------------------------------
# the loop keeps one step in flight (PR 28): the same mathematics, the same
# batches in the same order, n pulls, drained at the call's end
# ---------------------------------------------------------------------------

LSTM = dict(dnn="lstm", dataset="ptb", batch_size=2, clip_norm=0.25,
            lr=0.5, momentum=0.9,
            model_kwargs=dict(embed_dim=16, hidden_dim=16),
            dataset_kwargs=dict(vocab_size=64, bptt=8,
                                synthetic_tokens_n=2 * 8 * 5 + 1))


def _state_leaves(t):
    import jax
    s = t.state
    return {"/".join(str(k) for k in path): np.asarray(jax.device_get(leaf))
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                (s.params, s.model_state, s.opt_state, s.ef_residual,
                 s.carry, s.step))[0]}


def _train_losses(t):
    return [(r["step"], r["loss"]) for r in map(json.loads, open(
        os.path.join(t.run_dir, "metrics.jsonl")))
        if r.get("event") == "train"]


@pytest.mark.parametrize("kw", [
    dict(nworkers=1, compressor="auto", compress_warmup_steps=0),
    dict(nworkers=8, compressor="gaussian", compress_warmup_steps=0),
    dict(nworkers=1, compressor="none"),
    dict(nworkers=8, compressor="none"),
    # the carry is reset at the epoch wrap (4 steps an epoch), by the step
    # number, on the state's futures
    dict(nworkers=1, compressor="gaussian", compress_warmup_steps=2, **LSTM),
], ids=["sparse-1", "sparse-8", "dense-1", "dense-8", "lstm-epoch-wrap"])
def test_train_n_is_n_times_train_1_bit_for_bit(tmp_path, kw):
    """`train(n)` runs ahead of all its steps but the last; n calls of
    `train(1)` never do. Parameters, momentum, residual, carry and every
    step's loss are the same bits."""
    n = 7
    cfg = dict(max_steps=20, log_every=1, momentum=0.9, weight_decay=1e-4)
    cfg.update(kw)
    ahead = Trainer(make_cfg(tmp_path / "ahead", **cfg))
    ahead.train(n)
    blocking = Trainer(make_cfg(tmp_path / "blocking", **cfg))
    for _ in range(n):
        blocking.train(1)
    if kw.get("dnn") == "lstm":
        assert ahead.recurrent and 1 < ahead.steps_per_epoch < n - 1
    a, b = _state_leaves(ahead), _state_leaves(blocking)
    assert a.keys() == b.keys() and len(a) > 4
    for path in a:
        assert a[path].tobytes() == b[path].tobytes(), path
    assert _train_losses(ahead) == _train_losses(blocking)
    assert [s for s, _ in _train_losses(ahead)] == list(range(1, n + 1))
    ahead.close()
    blocking.close()


def test_train_pulls_exactly_n_batches_and_ends_on_its_last_step(tmp_path):
    """What the callers of `train(n, data_iter)` rely on: n pulls from the
    iterator they gave, and on return every step has ended: `_state`,
    `_step_cache` and `_probe_batch` are the last step's, nothing is in
    flight."""
    import jax
    t = Trainer(make_cfg(tmp_path, nworkers=1, max_steps=40, log_every=4))

    class Counted:
        def __init__(self, it):
            self.it, self.pulled = it, []

        def __iter__(self):
            return self

        def __next__(self):
            self.pulled.append(next(self.it))
            return self.pulled[-1]

    feed = Counted(t._train_iter())
    for n, total in ((5, 5), (1, 6), (3, 9)):
        rec = t.train(n, data_iter=feed)
        assert len(feed.pulled) == total
        assert t._step_cache == total and t._flight is None
        assert all(leaf.is_ready()
                   for leaf in jax.tree_util.tree_leaves(t._state))
        assert int(jax.device_get(t._state.step)) == total
        for mine, fed in zip(jax.tree_util.tree_leaves(t._probe_batch),
                             feed.pulled[-1]):
            np.testing.assert_array_equal(np.asarray(mine), fed)
        assert rec["event"] == "train"
    # train(5) logged step 4 and returned it; train(1) and train(3) end
    # between log steps on the quiet record of their own last step
    assert rec["step"] == 8
    t.close()


@pytest.mark.parametrize("nworkers", [1, 8])
def test_the_log_lines_lr_runs_no_device_program(nworkers):
    """`_log_train` asks the schedule with a Python int while the next
    step is in flight; an eager jnp expression would queue its programs
    behind that step and hold the loop until it ends (on the chip the log
    step took a whole step, PERF.md PR 28). A Python number is worked out
    in numpy and agrees with what the jitted step computes."""
    import jax
    import jax.numpy as jnp
    from gaussiank_sgd_tpu.training.lr_schedule import (
        warmup_milestone_schedule)
    sched = warmup_milestone_schedule(0.1, nworkers, 7, 100, 2.5,
                                      (0.5, 0.75), 0.1)
    for step in (0, 3, 17, 18, 49, 50, 74, 75, 99):
        host = sched(step)
        assert not isinstance(host, jax.Array)
        assert float(host) == pytest.approx(
            float(jax.jit(sched)(jnp.asarray(step))), rel=1e-6)
    assert isinstance(sched(jnp.asarray(3)), jax.Array)
