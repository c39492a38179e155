"""Step-timeline tracing.

Covers the observability layer this PR adds on top of the event bus
(docs/OBSERVABILITY.md "Tracing & trajectory"): TraceContext span
emission, thread-local stamping and the trace-off byte-identity
guarantee; the trace CLI round-trip on a LIVE traced run (the host spans
of the loop, rendered by their own clock); and the chaos span tree
(rollback span parented to the dying trajectory, rotated root afterwards).
"""

import json
import os

import pytest

from gaussiank_sgd_tpu.telemetry import (EventBus, JSONLExporter,
                                         MemoryExporter, TraceContext,
                                         build_chrome_trace,
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
