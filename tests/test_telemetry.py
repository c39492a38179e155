"""Telemetry subsystem (docs/OBSERVABILITY.md): the event bus envelope
contract, exporters, schema/stream validation, the skipped-step-aware
throughput tracker, profiler session hooks, the trainer integration (on-
device comms accounting in the JSONL stream), and the ISSUE acceptance
scenario — a chaos-NaN run whose single JSONL stream validates strictly
and whose timing/comms summaries the report CLI reconstructs from the
file alone.
"""

import json
import os
import threading

import numpy as np
import pytest

import jax

from gaussiank_sgd_tpu.telemetry import (
    SCHEMA_VERSION, EventBus, JSONLExporter, MemoryExporter,
    PrometheusTextfileExporter, ThroughputTracker, validate_record,
    validate_stream,
)
from gaussiank_sgd_tpu.telemetry.events import validate_file
from gaussiank_sgd_tpu.telemetry.profiler import ProfilerSession
from gaussiank_sgd_tpu.telemetry.report import (format_report, load_events,
                                                summarize)
from gaussiank_sgd_tpu.telemetry.__main__ import main as telemetry_cli
from gaussiank_sgd_tpu.training import chaos
from gaussiank_sgd_tpu.training.config import TrainConfig
from gaussiank_sgd_tpu.training.trainer import Trainer


# ---------------------------------------------------------------- event bus

def test_bus_stamps_envelope_and_orders_seq():
    mem = MemoryExporter()
    bus = EventBus([mem], clock=lambda: 123.456789)
    src = {"event": "skip", "step": 3, "nonfinite": 1.0}
    out = bus.emit("skip", step=3, nonfinite=1.0)
    bus.publish(src)
    assert "seq" not in src, "publish must not mutate the caller's dict"
    recs = mem.records
    assert [r["seq"] for r in recs] == [0, 1]
    assert all(r["schema_version"] == SCHEMA_VERSION for r in recs)
    assert all(r["ts"] == 123.456789 for r in recs)
    assert out == recs[0]
    assert bus.seq == 2


def test_bus_requires_event_and_rejects_after_close(tmp_path):
    bus = EventBus([MemoryExporter()])
    with pytest.raises(ValueError, match="event"):
        bus.publish({"step": 1})
    bus.close()
    bus.close()                           # idempotent
    with pytest.raises(ValueError, match="closed"):
        bus.emit("skip", step=1, nonfinite=0.0)


def test_bus_validate_mode_raises_on_schema_violation():
    bus = EventBus([MemoryExporter()], validate=True)
    bus.emit("skip", step=1, nonfinite=2.0)          # well-formed: fine
    with pytest.raises(ValueError, match="missing required field"):
        bus.emit("skip", step=1)                     # nonfinite missing


def test_bus_concurrent_publishes_keep_file_order_equal_seq_order(tmp_path):
    """The delivery turnstile serializes fan-out in ticket order, so the
    JSONL file order must equal seq order even with many publisher
    threads (the prefetch-thread scenario)."""
    path = str(tmp_path / "t.jsonl")
    bus = EventBus([JSONLExporter(path)])
    n_threads, per_thread = 8, 50

    def worker(i):
        for j in range(per_thread):
            bus.emit("skip", step=i * per_thread + j, nonfinite=0.0)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    bus.close()
    seqs = [json.loads(l)["seq"] for l in open(path)]
    assert seqs == list(range(n_threads * per_thread))


def test_bus_fanout_runs_outside_the_bus_lock():
    """Regression for the gklint conc-callback-under-lock finding: the
    exporter fan-out must run with the bus lock RELEASED (a slow exporter
    stalls later deliveries — the ordering contract — but never seq
    assignment, attach, or set_stamp), while still delivering in strict
    seq order across publisher threads."""
    bus = EventBus([])
    seen = []

    class LockProbe(MemoryExporter):
        def emit(self, record):
            seen.append((record["seq"], bus._lock.locked()))
            super().emit(record)

    bus.attach(LockProbe())
    n_threads, per_thread = 4, 25

    def worker(i):
        for j in range(per_thread):
            bus.emit("skip", step=i * per_thread + j, nonfinite=0.0)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    bus.close()
    assert [s for s, _ in seen] == list(range(n_threads * per_thread))
    assert not any(locked for _, locked in seen), \
        "exporter invoked while the bus lock was held"


def test_bus_validate_failure_retires_ticket_without_wedging():
    """A publish that fails validation has already taken a seq ticket;
    the turnstile must retire it (seq gap, like before) instead of
    leaving every later publisher waiting on an undelivered ticket."""
    mem = MemoryExporter()
    bus = EventBus([mem], validate=True)
    bus.emit("skip", step=1, nonfinite=0.0)            # seq 0
    with pytest.raises(ValueError, match="missing required field"):
        bus.emit("skip", step=1)                       # seq 1, retired
    rec = bus.emit("skip", step=2, nonfinite=0.0)      # must not deadlock
    assert rec["seq"] == 2
    assert [r["seq"] for r in mem.records] == [0, 2]


# ---------------------------------------------------------------- exporters

def test_jsonl_exporter_modes_and_none_path(tmp_path):
    path = str(tmp_path / "e.jsonl")
    ex = JSONLExporter(path)
    ex.emit({"event": "a", "x": 1})
    ex.close()
    ex = JSONLExporter(path)                  # default append
    ex.emit({"event": "b"})
    ex.close()
    assert [json.loads(l)["event"] for l in open(path)] == ["a", "b"]
    ex = JSONLExporter(path, mode="w")        # truncate
    ex.emit({"event": "c"})
    ex.close()
    assert [json.loads(l)["event"] for l in open(path)] == ["c"]
    with pytest.raises(ValueError, match="mode"):
        JSONLExporter(path, mode="x")
    JSONLExporter(None).emit({"event": "noop"})   # no-op sink, no crash


def test_memory_exporter_ring_capacity():
    mem = MemoryExporter(capacity=3)
    for i in range(5):
        mem.emit({"event": "train", "step": i})
    assert [r["step"] for r in mem.records] == [2, 3, 4]
    assert mem.events("train")[-1]["step"] == 4
    mem.clear()
    assert mem.records == []
    with pytest.raises(ValueError):
        MemoryExporter(capacity=0)


def test_prometheus_textfile_exporter(tmp_path):
    path = str(tmp_path / "gksgd.prom")
    ex = PrometheusTextfileExporter(path)
    ex.emit({"event": "train", "loss": 2.5, "step": 10, "skipped": False,
             "note": "strings are skipped", "sel_per_bucket": [1, 2]})
    ex.emit({"event": "train", "loss": 2.25, "step": 11, "skipped": True})
    ex.close()
    text = open(path).read()
    lines = dict(l.rsplit(" ", 1) for l in text.splitlines()
                 if l and not l.startswith("#"))
    assert lines['gksgd_events_total{event="train"}'] == "2"
    assert float(lines["gksgd_train_loss"]) == 2.25        # latest wins
    assert float(lines["gksgd_train_skipped"]) == 1        # bool -> int
    assert "gksgd_train_note" not in lines                 # non-numeric
    assert "gksgd_train_sel_per_bucket" not in lines
    assert not [f for f in os.listdir(tmp_path)
                if ".tmp." in f], "tmp file must be renamed away"


def test_prometheus_comms_counters_accumulate(tmp_path):
    """bytes_sent/overlapped_bytes_sent additionally export as monotonic
    *_total counters (rate()-able wire traffic), while a level such as
    ef_norm stays a latest-value gauge — and the write is still
    tmp+rename."""
    path = str(tmp_path / "gksgd.prom")
    ex = PrometheusTextfileExporter(path)
    for ef_norm in (2.0, 1.5):
        ex.emit({"event": "train", "step": 1, "bytes_sent": 100,
                 "overlapped_bytes_sent": 60, "ef_norm": ef_norm})
    ex.emit({"event": "skip", "step": 2, "nonfinite": 1.0})  # no counters
    ex.close()
    lines = dict(l.rsplit(" ", 1) for l in open(path).read().splitlines()
                 if l and not l.startswith("#"))
    assert float(lines["gksgd_train_bytes_sent_total"]) == 200
    assert float(lines["gksgd_train_overlapped_bytes_sent_total"]) == 120
    assert float(lines["gksgd_train_bytes_sent"]) == 100       # gauge: last
    assert float(lines["gksgd_train_ef_norm"]) == 1.5
    assert "gksgd_skip_step_total" not in lines
    assert not [f for f in os.listdir(tmp_path) if ".tmp." in f]


# --------------------------------------------------------------- validation

def test_validate_record_compat_and_strict():
    # legacy pre-telemetry record: no envelope — old readers keep working
    legacy = {"event": "train", "step": 1, "epoch": 0, "loss": 1.0,
              "lr": 0.1, "grad_norm": 1.0, "num_selected": 5.0,
              "bytes_sent": 40, "density": 0.01, "io_s": 0.0,
              "step_s": 0.1, "skipped": 0.0, "nonfinite": 0.0,
              "top1": 0.5}                    # extra aux field: tolerated
    assert validate_record(legacy) == []
    errs = validate_record(legacy, strict=True)
    assert any("schema_version" in e for e in errs)
    # unknown event kinds pass non-strict (forward compat), fail strict
    assert validate_record({"event": "future_thing"}) == []
    assert validate_record({"event": "future_thing"}, strict=True)
    # type mismatch is always an error
    bad = dict(legacy, loss="NaN-ish")
    assert any("loss" in e for e in validate_record(bad))
    assert any("newer than this reader" in e for e in validate_record(
        {"event": "skip", "step": 1, "nonfinite": 0.0,
         "schema_version": SCHEMA_VERSION + 1}))


def test_validate_stream_gaps_resets_truncation():
    def line(seq):
        return json.dumps({"event": "skip", "step": seq, "nonfinite": 0.0,
                           "schema_version": 1, "seq": seq, "ts": 0.0})
    rep = validate_stream([line(0), line(1), line(2)], strict=True)
    assert rep.ok and rep.n_records == 3 and rep.n_stamped == 3
    # a gap warns (dropped records) but stays legal
    rep = validate_stream([line(0), line(3)])
    assert rep.ok and rep.seq_gaps == 1 and "missing" in rep.warnings[0]
    # a reset marks a concatenated mixed-run file
    rep = validate_stream([line(5), line(0)])
    assert rep.seq_resets == 1
    # a partial FINAL line is truncation (fatal); mid-stream noise is not
    rep = validate_stream([line(0), '{"event": "tr'])
    assert rep.truncated and not rep.ok
    rep = validate_stream(['{"bad', line(0)])
    assert not rep.truncated and not rep.ok     # still an error, not trunc


# --------------------------------------------- throughput tracker satellite

def test_tracker_skipped_steps_do_not_inflate_ex_per_s():
    """The satellite contract: a guard-skipped step burns wall-clock but
    contributes ZERO examples, so ex/s must drop, not hold."""
    tr = ThroughputTracker(window=10)
    for _ in range(4):
        tr.update(32, 0.1)
    assert tr.examples_per_s == pytest.approx(320.0)
    for _ in range(4):
        tr.update(32, 0.1, skipped=True)
    # 4 useful steps of 8 total: exactly half the naive number
    assert tr.examples_per_s == pytest.approx(160.0)
    assert tr.skipped_in_window == 4
    assert tr.steps_per_s == pytest.approx(4 / 0.8)


def test_tracker_reset_on_rollback_forgets_old_trajectory():
    tr = ThroughputTracker(window=10)
    for _ in range(5):
        tr.update(32, 0.1, skipped=True)
    tr.reset()
    assert len(tr) == 0 and tr.examples_per_s is None
    tr.update(32, 0.1)
    assert tr.examples_per_s == pytest.approx(320.0), \
        "post-rollback window must not average the abandoned trajectory"


def test_tracker_window_mfu_and_validation():
    with pytest.raises(ValueError):
        ThroughputTracker(window=0)
    tr = ThroughputTracker(window=2)
    with pytest.raises(ValueError):
        tr.update(32, -1.0)
    assert tr.examples_per_s is None and tr.steps_per_s is None
    tr.update(10, 1.0)
    tr.update(10, 1.0)
    tr.update(90, 1.0)                       # rolls the first sample out
    assert tr.examples_per_s == pytest.approx(50.0)
    # mfu: 1 step/s at 2e12 flops/step on a 4e12-peak chip = 0.5
    assert tr.mfu(2e12, 4e12) == pytest.approx(0.5)
    assert tr.mfu(None, 4e12) is None and tr.mfu(2e12, None) is None


def test_tracker_signals_snapshot_is_one_canonical_view():
    """signals() returns every derived figure from ONE lock acquisition
    and each field equals its standalone property — the policy engine
    and the telemetry report CLI must read the same numbers (ISSUE 6
    satellite)."""
    from gaussiank_sgd_tpu.telemetry import ThroughputSignals

    tr = ThroughputTracker(window=4, ema_beta=0.5)
    for i in range(3):
        tr.update(32, 0.1 * (i + 1), skipped=(i == 1))
    sig = tr.signals(flops_per_step=2e12, peak_flops=4e12)
    assert isinstance(sig, ThroughputSignals)
    assert sig.window_steps == len(tr) == 3
    assert sig.skipped_in_window == tr.skipped_in_window == 1
    assert sig.total_seconds == pytest.approx(tr.total_seconds)
    assert sig.examples_per_s == pytest.approx(tr.examples_per_s)
    assert sig.steps_per_s == pytest.approx(tr.steps_per_s)
    assert sig.step_s_ema == pytest.approx(tr.step_s_ema)
    assert sig.mfu == pytest.approx(tr.mfu(2e12, 4e12))
    # EMA weights the recent samples (beta=0.5 over 0.1, 0.2, 0.3)
    assert 0.1 < sig.step_s_ema < 0.3
    # without flops context the snapshot still carries the timing fields
    bare = tr.signals()
    assert bare.mfu is None and bare.step_s_ema == sig.step_s_ema
    # reset drops the EMA too: a restored run rebuilds its own trajectory
    tr.reset()
    assert tr.signals().step_s_ema is None
    assert tr.signals().window_steps == 0


# ------------------------------------------------------------------ profiler

def test_profiler_session_window_and_close(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop", None)))
    mem = MemoryExporter()
    bus = EventBus([mem])
    with pytest.raises(ValueError, match="empty"):
        ProfilerSession("/tmp/p", 5, 5)
    with pytest.raises(ValueError, match="negative"):
        ProfilerSession("/tmp/p", -1, 5)
    s = ProfilerSession("/tmp/p", 2, 4, bus=bus)
    s.maybe_transition(0)
    assert not s.active
    s.maybe_transition(3)                 # late entry still starts
    assert s.active and calls == [("start", "/tmp/p")]
    s.maybe_transition(4)
    assert not s.active and calls[-1] == ("stop", None)
    s.maybe_transition(2)                 # one window per session
    assert not s.active
    assert [(r["action"], r["step"]) for r in mem.events("profile")] == [
        ("start", 3), ("stop", 4)]
    # close() stops a live trace
    calls.clear()
    s2 = ProfilerSession("/tmp/p", 0, 100, bus=bus)
    s2.maybe_transition(0)
    s2.close()
    assert calls == [("start", "/tmp/p"), ("stop", None)]


# --------------------------------------------------------- trainer integration

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


def test_trainer_stream_carries_accounting_and_envelope(tmp_path):
    """The rewired trainer: every record seq-stamped in file order, and
    the train records carry the on-device accounting — dense warmup has
    density 1.0 / zero EF, sparse steps land near the target density with
    a growing committed-EF norm and a positive ex/s."""
    t = Trainer(make_cfg(tmp_path, max_steps=10, log_every=2,
                         save_every_steps=5,
                         prom_textfile=str(tmp_path / "gksgd.prom")))
    t.fit()
    t.close()
    recs = read_events(t)
    assert [r["seq"] for r in recs] == list(range(len(recs)))
    assert all(r["schema_version"] == SCHEMA_VERSION for r in recs)
    assert recs[0]["event"] == "config"
    kinds = {r["event"] for r in recs}
    assert {"config", "train", "checkpoint"} <= kinds

    train = read_events(t, "train")
    warm = [r for r in train if r["step"] <= 4]
    sparse = [r for r in train if r["step"] > 4]
    assert warm and sparse
    for r in warm:
        assert r["density_achieved"] == pytest.approx(1.0)
        assert r["ef_norm"] == 0.0
    for r in sparse:
        # gaussian threshold selection: genuinely sparse (the threshold
        # may under-fill k on a tiny model, so only an upper band is safe)
        assert 0.0 < r["density_achieved"] < 0.01 * 3
        assert r["ef_norm"] > 0.0
        assert r["bytes_sent"] > 0
    assert all(r["ex_per_s"] > 0 for r in train)
    # single-bucket mnistnet plan: no redundant per-bucket column
    assert all("sel_per_bucket" not in r for r in train)

    # strict validation of the freshly written stream (the CI contract)
    rep = validate_file(os.path.join(t.run_dir, "metrics.jsonl"),
                        strict=True)
    assert rep.ok, rep.errors
    assert rep.seq_gaps == 0 and rep.seq_resets == 0
    # the Prometheus textfile exporter rode the same bus
    prom = open(tmp_path / "gksgd.prom").read()
    assert 'gksgd_events_total{event="train"}' in prom
    assert "gksgd_train_loss" in prom


def test_trainer_multi_bucket_logs_sel_per_bucket(tmp_path):
    t = Trainer(make_cfg(tmp_path, max_steps=6, log_every=6,
                         compress_warmup_steps=0, bucket_size=1 << 18,
                         bucket_policy="uniform"))
    assert len(t.plan.buckets) > 1
    t.train(6)
    t.close()
    train = read_events(t, "train")
    assert train
    for r in train:
        assert len(r["sel_per_bucket"]) == len(t.plan.buckets)
        assert sum(r["sel_per_bucket"]) == pytest.approx(
            r["num_selected"], rel=0.05)
    rep = validate_file(os.path.join(t.run_dir, "metrics.jsonl"),
                        strict=True)
    assert rep.ok, rep.errors
    t.close()


# ------------------------------------------------------- report + CLI

def test_report_summarize_reconstructs_run(tmp_path):
    path = str(tmp_path / "run.jsonl")
    bus = EventBus([JSONLExporter(path)])
    bus.emit("config", dnn="resnet20", dataset="cifar10", batch_size=32,
             compressor="gaussian", density=0.01, lr=0.1, nworkers=8,
             n_params=1000, total_steps=100)
    for step, (loss, io_s, step_s, b) in enumerate(
            [(2.0, 0.01, 0.1, 800), (1.5, 0.03, 0.2, 820)], start=1):
        bus.emit("train", step=step * 50, epoch=0, loss=loss, lr=0.1,
                 grad_norm=1.0, num_selected=10.0, bytes_sent=b,
                 density=0.01, density_achieved=0.0101, ef_norm=3.0,
                 io_s=io_s, step_s=step_s, skipped=0.0, nonfinite=0.0,
                 ex_per_s=320.0)
    bus.emit("skip", step=7, nonfinite=4.0)
    bus.emit("rollback", reason="skip_budget", rollback=1, to_step=4,
             lr_scale=0.5, checkpoint="ckpt/step_00000004")
    bus.emit("eval", step=100, epoch=1, val_loss=1.2, top1=0.7)
    bus.close()

    s = summarize(load_events(path))
    assert s["run"]["dnn"] == "resnet20" and s["run"]["n_params"] == 1000
    assert s["steps"]["last_step"] == 100
    assert s["timing"]["io_s_mean"] == pytest.approx(0.02)
    assert s["timing"]["step_s_mean"] == pytest.approx(0.15)
    assert s["throughput"]["ex_per_s_mean"] == pytest.approx(320.0)
    assert s["comms"]["bytes_per_step_worker_mean"] == pytest.approx(810)
    assert s["comms"]["est_total_bytes_per_worker"] == 81000
    assert s["comms"]["est_total_bytes_all_workers"] == 648000
    assert s["compression"]["bytes_vs_dense"] == pytest.approx(
        810 / 4000.0)
    assert s["resilience"]["skips"] == 1
    assert s["resilience"]["rollbacks"] == 1
    assert s["resilience"]["last_rollback"]["to_step"] == 4
    assert s["eval_last"]["top1"] == 0.7

    text = format_report(s)
    for needle in ("== per-phase timing", "== comms volume",
                   "== compression efficiency", "== resilience",
                   "resnet20", "skip_budget"):
        assert needle in text


def test_cli_report_and_validate(tmp_path, capsys):
    path = str(tmp_path / "run.jsonl")
    bus = EventBus([JSONLExporter(path)])
    bus.emit("skip", step=1, nonfinite=2.0)
    bus.close()
    assert telemetry_cli(["validate", path, "--strict"]) == 0
    out = capsys.readouterr().out
    assert out.startswith("OK") and "skip=1" in out
    assert telemetry_cli(["report", path, "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["resilience"]["skips"] == 1
    # a truncated stream fails validation with exit 1
    with open(path, "a") as fh:
        fh.write('{"event": "tr')
    assert telemetry_cli(["validate", path]) == 1
    assert telemetry_cli(["report", str(tmp_path / "nope.jsonl")]) == 2


# --------------------------------------------------------- ISSUE acceptance

def test_acceptance_chaos_nan_stream_validates_and_reports(tmp_path):
    """ISSUE acceptance: a CPU chaos-NaN run (guard skip -> skip-budget
    rollback) plus a transient loader fault emits ONE JSONL stream that
    validates strictly (train/io/comms/resilience events all present),
    and `telemetry report` reconstructs the per-phase timing and
    bytes-sent summaries from the file alone."""
    t = Trainer(make_cfg(tmp_path, max_steps=12, log_every=2,
                         save_every_steps=4, max_consecutive_skips=1,
                         io_backoff_s=0.001))
    flaky = chaos.FlakyEpochSource(t.train_ds, fail_batches=[2], times=1)
    t.train_ds = flaky
    chaos.inject_nan_batches(t, {6})       # poisons step 7 -> rollback to 4
    while t.step < t.total_steps:
        t.train(t.total_steps - t.step)
    t.close()

    path = os.path.join(t.run_dir, "metrics.jsonl")
    rep = validate_file(path, strict=True)
    assert rep.ok, rep.errors
    assert rep.seq_gaps == 0 and rep.seq_resets == 0 and not rep.truncated
    kinds = set(rep.events)
    assert {"config", "train", "skip", "rollback", "checkpoint",
            "io_retry"} <= kinds, kinds

    events = load_events(path)
    s = summarize(events)
    train = [e for e in events if e["event"] == "train"]
    # the report's timing/comms numbers ARE the stream's (file-only
    # reconstruction): recompute independently and compare exactly
    assert s["timing"]["io_s_mean"] == pytest.approx(
        np.mean([r["io_s"] for r in train]))
    assert s["timing"]["step_s_mean"] == pytest.approx(
        np.mean([r["step_s"] for r in train]))
    assert s["comms"]["bytes_per_step_worker_mean"] == pytest.approx(
        np.mean([r["bytes_sent"] for r in train]))
    assert s["steps"]["last_step"] == 12
    assert s["resilience"]["skips"] == 1
    assert s["resilience"]["rollbacks"] == 1
    assert s["resilience"]["last_rollback"]["to_step"] == 4
    assert s["resilience"]["io_retries"] == 1
    assert s["resilience"]["checkpoints"] >= 2
    # sparse intervals carried the on-device accounting through the chaos
    sparse = [r for r in train if r["step"] > 4 and not r["skipped"]]
    assert sparse and all(r["bytes_sent"] > 0 for r in sparse)
    text = format_report(s)
    assert "rollbacks=1" in text and "io_retries=1" in text


def test_report_program_audit_join(tmp_path):
    """``report --audit``: the run's (compressor, wire, overlap) key joins
    to exactly the audited arms with the same key; a stream that recorded
    no key fields matches nothing (an all-arms match would misread as a
    certification)."""
    audit = {
        "git_rev": "abc1234", "jax_version": jax.__version__, "ok": True,
        "arms": {
            "pipe_wire": {"fingerprint": "f" * 16,
                          "wire_format": "u16bf16", "overlap": "pipelined",
                          "config": {"selector": "topk"}},
            "seq_legacy": {"fingerprint": "0" * 16,
                           "wire_format": "i32f32", "overlap": "off",
                           "config": {"selector": "topk"}},
            "dense": {"fingerprint": "d" * 16,
                      "wire_format": "i32f32", "overlap": "off",
                      "config": {"selector": "topk", "dense": True}},
        },
    }
    events = [
        {"event": "config", "schema_version": 1, "compressor": "topk"},
        {"event": "train", "schema_version": 1, "step": 1,
         "wire_format": "u16bf16", "overlap": "pipelined"},
    ]
    s = summarize(events, audit=audit)
    pa = s["program_audit"]
    assert pa["audit_git_rev"] == "abc1234"
    assert pa["run_program_key"]["wire_format"] == "u16bf16"
    assert [m["arm"] for m in pa["matched_arms"]] == ["pipe_wire"]
    text = format_report(s)
    assert "program audit join" in text and "pipe_wire" in text

    # keyless stream: no match, and the report says so rather than
    # listing every arm
    s2 = summarize([{"event": "checkpoint", "schema_version": 1}],
                   audit=audit)
    assert s2["program_audit"]["matched_arms"] == []
    assert "no audited arm matches" in format_report(s2)

    # the CLI surfaces the join and exits 2 on an unreadable artifact
    ev_path = os.path.join(str(tmp_path), "ev.jsonl")
    with open(ev_path, "w", encoding="utf-8") as fh:
        for e in events:
            fh.write(json.dumps(e) + "\n")
    audit_path = os.path.join(str(tmp_path), "audit.json")
    with open(audit_path, "w", encoding="utf-8") as fh:
        json.dump(audit, fh)
    assert telemetry_cli(["report", ev_path, "--audit", audit_path]) == 0
    assert telemetry_cli(["report", ev_path, "--audit",
                          os.path.join(str(tmp_path), "nope.json")]) == 2
