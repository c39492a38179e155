"""Step-timeline tracing + cross-run regression sentinel.

Covers the observability layer this PR adds on top of the event bus
(docs/OBSERVABILITY.md "Tracing & trajectory"): TraceContext span
emission, thread-local stamping and the trace-off byte-identity
guarantee; the trace CLI round-trip on a LIVE traced run (the host spans
of the loop, rendered by their own clock); the chaos span tree (rollback span parented to the dying
trajectory, rotated root afterwards); and the regression sentinel's
noise-floored classification over the committed bench history.
"""

import json
import os

import pytest

from analysis.regression_sentinel import (_perturb, classify_config,
                                          compare, pick_baseline)
from analysis.regression_sentinel import main as sentinel_main
from gaussiank_sgd_tpu.telemetry import (EventBus, JSONLExporter,
                                         MemoryExporter, TraceContext,
                                         append_history,
                                         build_chrome_trace,
                                         build_history_record, load_history,
                                         validate_stream)
from gaussiank_sgd_tpu.telemetry.__main__ import main as telemetry_cli
from gaussiank_sgd_tpu.telemetry.events import validate_file
from gaussiank_sgd_tpu.training import chaos
from gaussiank_sgd_tpu.training.config import TrainConfig
from gaussiank_sgd_tpu.training.trainer import Trainer


def make_cfg(tmp_path, **kw):
    base = dict(
        dnn="mnistnet", dataset="mnist", batch_size=8, nworkers=8,
        lr=0.05, momentum=0.9, weight_decay=0.0, epochs=1, max_steps=12,
        compressor="gaussian", density=0.01, compress_warmup_steps=4,
        warmup_epochs=0.0, compute_dtype="float32", output_dir=str(tmp_path),
        log_every=5, eval_every_epochs=0, save_every_epochs=0, seed=0,
        trace="on",
    )
    base.update(kw)
    return TrainConfig(**base)


def read_events(t):
    return [json.loads(line) for line in
            open(os.path.join(t.run_dir, "metrics.jsonl"))]


def spans(events, name=None, ph=None):
    out = [r for r in events if r.get("event") == "span"]
    if name is not None:
        out = [r for r in out if r.get("name") == name]
    if ph is not None:
        out = [r for r in out if r.get("ph") == ph]
    return out


# ------------------------------------------------------------ TraceContext

def test_trace_context_nesting_stamp_and_uninstall():
    """Nested spans parent correctly, every record published while a span
    is open is stamped with trace_id + the INNERMOST span id, and after
    uninstall() the stream reverts to stamp-free (byte-identity)."""
    mem = MemoryExporter()
    bus = EventBus([mem])
    tc = TraceContext(bus, trace_id="t-test").install()
    traj = tc.begin("trajectory", step=0)
    with tc.span("outer") as outer_sid:
        with tc.span("inner"):
            bus.emit("skip", step=1, nonfinite=1.0)
    tc.drain()
    tc.end(traj)
    tc.uninstall()
    bus.emit("skip", step=2, nonfinite=1.0)
    recs = mem.records

    inner = next(r for r in recs if r.get("name") == "inner")
    outer = next(r for r in recs if r.get("name") == "outer")
    assert inner["parent_span"] == outer_sid == outer["span_id"]
    assert outer["parent_span"] == traj
    assert inner["ph"] == outer["ph"] == "X"
    assert inner["dur_ns"] >= 0 and inner["t0_ns"] >= outer["t0_ns"]
    # the inner X record lands BEFORE the outer's (drained in close order)
    assert recs.index(inner) < recs.index(outer)

    stamped = next(r for r in recs
                   if r.get("event") == "skip" and r["step"] == 1)
    assert stamped["trace_id"] == "t-test"
    # innermost open span at publish time was "inner"'s sid
    assert stamped["span_id"] == inner["span_id"]
    unstamped = next(r for r in recs
                     if r.get("event") == "skip" and r["step"] == 2)
    assert "trace_id" not in unstamped and "span_id" not in unstamped

    lines = [json.dumps(r) for r in recs]
    rep = validate_stream(lines, strict=True)
    assert rep.ok, rep.errors
    assert rep.span_orphans == 0 and rep.span_unclosed == 0


def test_trace_context_stack_is_thread_local():
    """A publisher thread with no open span of its own gets trace_id but
    NOT the train loop's span_id (the prefetch thread contract)."""
    import threading
    mem = MemoryExporter()
    bus = EventBus([mem])
    tc = TraceContext(bus, trace_id="t-thr").install()
    with tc.span("main_loop"):
        th = threading.Thread(
            target=lambda: bus.emit("skip", step=9, nonfinite=0.0))
        th.start()
        th.join()
    rec = next(r for r in mem.records if r.get("event") == "skip")
    assert rec["trace_id"] == "t-thr" and "span_id" not in rec


def test_validate_stream_flags_orphans_and_unclosed():
    """Span-tree health is WARNINGS, never errors: an undeclared parent
    and a B without E degrade the report but keep it ok."""
    lines = [
        json.dumps({"event": "span", "schema_version": 1, "seq": 0,
                    "ts": 1.0, "name": "trajectory", "span_id": "s01",
                    "ph": "B"}),
        json.dumps({"event": "span", "schema_version": 1, "seq": 1,
                    "ts": 2.0, "name": "ghost_child", "span_id": "s02",
                    "ph": "X", "parent_span": "never_declared"}),
    ]
    rep = validate_stream(lines, strict=True)
    assert rep.ok, rep.errors
    assert rep.span_orphans == 1 and rep.span_unclosed == 1
    assert any("orphan" in w for w in rep.warnings)
    assert any("never closed" in w for w in rep.warnings)


# ------------------------------------------------------- live round-trip

def test_trace_cli_round_trip_on_live_run(tmp_path, capsys):
    """ISSUE acceptance (trace half): a live traced run's JSONL validates
    strictly with a healthy span tree, the loop's host spans nest under
    one `iteration` per step and those under the trajectory, and the trace
    CLI renders them to Chrome-trace JSON on their own clock: every leaf
    inside its iteration, dispatches in step order."""
    t = Trainer(make_cfg(tmp_path, overlap="auto", bucket_size=8192,
                         bucket_policy="uniform", save_every_steps=6))
    t.train(12)
    t.close()
    path = os.path.join(t.run_dir, "metrics.jsonl")

    rep = validate_file(path, strict=True)
    assert rep.ok, rep.errors
    assert rep.span_orphans == 0 and rep.span_unclosed == 0
    assert rep.events.get("span", 0) >= 10

    events = read_events(t)
    traj = spans(events, name="trajectory", ph="B")
    assert len(traj) == 1
    traj_sid = traj[0]["span_id"]
    iters = spans(events, name="iteration", ph="X")
    assert [s["step"] for s in iters] == list(range(1, 13))
    assert all(s["parent_span"] == traj_sid for s in iters)
    iter_ids = {s["span_id"] for s in iters}
    for name in ("data_wait", "h2d", "step_dispatch", "step_sync",
                 "step_readback"):
        xs = spans(events, name=name, ph="X")
        assert len(xs) == 12, f"{name}: {len(xs)} spans for 12 steps"
        assert all(s["parent_span"] in iter_ids for s in xs)
    assert len(spans(events, name="log_step", ph="X")) == 2    # steps 5, 10
    saves = spans(events, name="checkpoint_save", ph="X")
    assert saves and all(s["parent_span"] in iter_ids for s in saves)
    dispatch_t0 = [s["t0_ns"] for s in spans(events, name="step_dispatch")]
    assert dispatch_t0 == sorted(dispatch_t0)
    # the reconstruction's fields are gone from the train records, the
    # stamp stays
    sparse_train = [r for r in events if r.get("event") == "train"
                    and "wire_format" in r]
    assert sparse_train
    assert all("pipeline_chunks" not in r and "comm_rounds" not in r
               and r["trace_id"] for r in sparse_train)

    out = str(tmp_path / "trace.json")
    rc = telemetry_cli(["trace", path, "-o", out])
    assert rc == 0
    assert "span(s)" in capsys.readouterr().out
    trace = json.load(open(out))
    evs = trace["traceEvents"]
    names = {e.get("name") for e in evs}
    assert {"trajectory", "iteration", "step_dispatch", "train"} <= names
    assert all(e["ts"] >= 0 for e in evs if "ts" in e)
    by_id = {e["args"]["span_id"]: e for e in evs
             if e.get("ph") == "X"}
    for e in by_id.values():
        parent = by_id.get(e["args"].get("parent_span"))
        if parent is not None:      # rendered to a tenth of a microsecond
            assert parent["ts"] - 0.2 <= e["ts"]
            assert e["ts"] + e["dur"] <= parent["ts"] + parent["dur"] + 0.2


def test_chaos_rollback_span_tree(tmp_path):
    """ISSUE acceptance (chaos half): a NaN-injected run that rolls back
    emits a well-formed span tree — the anomaly instant and the rollback
    span parent to the DYING trajectory, and a fresh trajectory root is
    opened for the restored run (both roots closed by the end)."""
    t = Trainer(make_cfg(tmp_path, max_steps=12, log_every=2,
                         save_every_steps=4, max_consecutive_skips=1))
    chaos.inject_nan_batches(t, {6})     # poisons step 7 -> rollback to 4
    while t.step < t.total_steps:
        t.train(t.total_steps - t.step)
    t.close()

    rep = validate_file(os.path.join(t.run_dir, "metrics.jsonl"),
                        strict=True)
    assert rep.ok, rep.errors
    assert rep.span_orphans == 0 and rep.span_unclosed == 0

    events = read_events(t)
    trajs = spans(events, name="trajectory", ph="B")
    assert len(trajs) == 2, "rollback must rotate the trajectory root"
    first, second = trajs[0]["span_id"], trajs[1]["span_id"]
    assert len(spans(events, name="trajectory", ph="E")) == 2

    by_id = {s["span_id"]: s for s in spans(events)
             if s["ph"] in ("X", "B")}

    def root_of(s):
        while s.get("parent_span") is not None:
            s = by_id[s["parent_span"]]
        return s["span_id"]

    # both happen inside an iteration of the dying trajectory
    rb = spans(events, name="rollback", ph="X")
    assert len(rb) == 1 and root_of(rb[0]) == first
    assert by_id[rb[0]["parent_span"]]["name"] == "iteration"
    assert rb[0]["reason"] == "skip_budget"
    anomaly = spans(events, name="anomaly_pending", ph="i")
    assert len(anomaly) == 1 and root_of(anomaly[0]) == first
    assert anomaly[0]["reason"] == "skip_budget"
    # post-rollback host spans hang off the NEW root
    post = [s for s in spans(events, name="checkpoint_save", ph="X")
            if root_of(s) == second]
    assert post, "restored trajectory sealed no checkpoint span"
    # the rollback event record itself is stamped into the old trajectory
    rb_ev = next(r for r in events if r.get("event") == "rollback")
    assert rb_ev["span_id"] == rb[0]["span_id"]


# ------------------------------------------------------ history + sentinel

def _history_rec(rev, ts, ratios=(0.90, 0.92), smoke=True, key="mnistnet"):
    med = sorted(ratios)[0]
    return {"history_schema": 1, "ts": ts, "git_rev": rev, "smoke": smoke,
            "platform": "cpu", "metric": "ratio_window_min_min",
            "value": med, "worst_config": key,
            "arms": {"wire": True, "overlap": True, "policy": None},
            "configs": {key: {
                "ratio_median": sum(ratios) / len(ratios),
                "ratio_window_min": med,
                "window_medians": list(ratios), "windows": len(ratios),
                "rounds": 12}}}


def test_history_record_round_trip(tmp_path):
    result = {"metric": "ratio_window_min_min", "value": 0.9,
              "detail": {"platform": "cpu", "worst_config": "mnistnet",
                         "configs": {"mnistnet": {
                             "ratio_median": 0.91, "ratio_window_min": 0.9,
                             "window_medians": [0.9, 0.92], "windows": 2,
                             "rounds": 12, "noise": "dropme",
                             "overlap_arm": {"exposed_seq_ms": 2.0,
                                             "n_buckets": 52}}}}}
    rec = build_history_record(result, smoke=True, ts=123.4567,
                               git_rev="abc1234")
    path = str(tmp_path / "hist.jsonl")
    append_history(path, rec)
    # a record from a FUTURE schema must be skipped, not fatal
    append_history(path, {"history_schema": 99, "git_rev": "future"})
    loaded = load_history(path)
    assert len(loaded) == 1
    got = loaded[0]
    assert got["git_rev"] == "abc1234" and got["smoke"] is True
    cell = got["configs"]["mnistnet"]
    assert cell["window_medians"] == [0.9, 0.92]
    assert "noise" not in cell          # only catalogued fields travel
    assert cell["overlap_arm"]["n_buckets"] == 52
    assert got["arms"]["overlap"] is True


def test_sentinel_detects_regression_and_ignores_jitter():
    """The classifier fires on a 10% ratio drop and stays quiet when the
    window medians move by round-to-round noise only (the reused
    noise_floored_delta_ms MAD floor)."""
    base = _history_rec("aaa0000", 100.0)
    degraded = _perturb(base, 0.90)
    v = compare(base, degraded, tol=0.05)
    assert v["status"] == "regressed" and v["n_regressed"] == 1
    assert v["worst_config"] == "mnistnet" and v["worst_delta"] < 0
    jittered = _perturb(base, 1.0, jitter=0.003)
    assert compare(base, jittered, tol=0.05)["status"] != "regressed"
    improved = _perturb(base, 1.10)
    assert compare(base, improved, tol=0.05)["status"] == "improved"


def test_sentinel_scalar_fallback_without_window_medians():
    a = _history_rec("aaa0000", 100.0)
    b = _history_rec("bbb1111", 200.0, ratios=(0.80, 0.82))
    for rec in (a, b):
        del rec["configs"]["mnistnet"]["window_medians"]
    status, delta = classify_config(a, b, "mnistnet", tol=0.05)
    assert status == "regressed" and delta == pytest.approx(-0.10, abs=1e-6)


def test_sentinel_baseline_scoping():
    """Baseline picking skips records with a different smoke flag, later
    timestamps, disjoint configs, and hand-authored synthetic rows."""
    hist = [
        _history_rec("real0000", 50.0, smoke=False),
        _history_rec("other000", 60.0, key="vgg16"),
        _history_rec("good0000", 70.0),
        _history_rec("new00000", 100.0),
    ]
    base = pick_baseline(hist, hist[-1], None, None)
    assert base is not None and base["git_rev"] == "good0000"
    only = [_history_rec("lonely00", 10.0)]
    assert pick_baseline(only, only[0], None, None) is None
    # a "synthetic": true seed row must never anchor a verdict on the
    # auto path — but an explicit --baseline-rev still reaches it
    fake = dict(_history_rec("fake0000", 80.0), synthetic=True)
    hist_f = [_history_rec("good0000", 70.0), fake,
              _history_rec("new00000", 100.0)]
    base = pick_baseline(hist_f, hist_f[-1], None, None)
    assert base is not None and base["git_rev"] == "good0000"
    newest = _history_rec("new00000", 100.0)
    assert pick_baseline([fake, newest], newest, None, None) is None
    explicit = pick_baseline(hist_f, hist_f[-1], "fake0000", None)
    assert explicit is not None and explicit["git_rev"] == "fake0000"


def test_sentinel_cli_end_to_end(tmp_path, capsys):
    """Exit codes + emitted event: 1 on regression (with a strict-valid
    bench_regression record for the policy signals to ingest), 0 on
    improvement, 0 with 'nothing to compare' on a single-record history,
    2 on an empty file."""
    hist = str(tmp_path / "hist.jsonl")
    base = _history_rec("aaa0000", 100.0)
    append_history(hist, base)
    append_history(hist, _perturb(base, 0.90))
    ev_path = str(tmp_path / "verdict.jsonl")
    rc = sentinel_main(["--history", hist, "--emit-event", ev_path])
    out = capsys.readouterr().out
    assert rc == 1 and "REGRESSED" in out and "bench trajectory" in out
    rep = validate_file(ev_path, strict=True)
    assert rep.ok, rep.errors
    verdict = json.loads(open(ev_path).read().strip())
    assert verdict["event"] == "bench_regression"
    assert verdict["status"] == "regressed"
    assert verdict["worst_config"] == "mnistnet"

    hist2 = str(tmp_path / "hist2.jsonl")
    append_history(hist2, base)
    append_history(hist2, _perturb(base, 1.10))
    assert sentinel_main(["--history", hist2]) == 0
    assert "IMPROVED" in capsys.readouterr().out

    hist3 = str(tmp_path / "hist3.jsonl")
    append_history(hist3, base)
    assert sentinel_main(["--history", hist3]) == 0
    assert "nothing to compare" in capsys.readouterr().out

    assert sentinel_main(["--history", str(tmp_path / "missing.jsonl")]) == 2
    capsys.readouterr()

    # --self-test: the CI wiring check passes on a real history
    assert sentinel_main(["--history", hist, "--self-test"]) == 0
    assert "self-test OK" in capsys.readouterr().out


def test_sentinel_verdict_feeds_policy_signals():
    """The emitted bench_regression record is ingestible by the policy
    engine's signals (the closed-loop satellite): regressed verdicts
    count, non-regressed ones don't."""
    from gaussiank_sgd_tpu.policy.signals import PolicySignals
    sig = PolicySignals()
    sig.update({"event": "bench_regression", "status": "regressed",
                "worst_config": "vgg16-u8192", "new_rev": "abc"})
    sig.update({"event": "bench_regression", "status": "improved",
                "new_rev": "def"})
    snap = sig.snapshot()
    assert snap.bench_regressions == 1
    assert snap.last_bench_regression == "vgg16-u8192"


def test_committed_history_is_sentinel_clean():
    """The repo's committed bench history must load, self-test, and not
    classify the committed tip as regressed — the CI gate's contract."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "analysis", "artifacts",
        "bench_history.jsonl")
    hist = load_history(path)
    assert hist, "committed bench_history.jsonl is missing or empty"
    assert all(r.get("history_schema") == 1 for r in hist)
    new = hist[-1]
    base = pick_baseline(hist, new, None, None)
    if base is not None:
        assert compare(base, new, tol=0.05)["status"] != "regressed"
