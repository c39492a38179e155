"""The program's own names for its work (docs/OBSERVABILITY.md "Tracing &
trajectory"): `jax.named_scope` phases in the lowered step programs, and the
host loop's spans — one `iteration` per step with leaf children, on one
clock, kept in memory between log steps and reachable after `close()`.
"""

import json
import os
import re

import pytest

from gaussiank_sgd_tpu.telemetry import (EventBus, MemoryExporter,
                                         TraceContext, tracing)
from gaussiank_sgd_tpu.training.config import TrainConfig
from gaussiank_sgd_tpu.training.trainer import Trainer

SPARSE_SCOPES = ("fwd_bwd", "flatten", "ef_select", "cand_topk", "pack",
                 "exchange", "scatter", "update", "guard", "step_metrics")
DENSE_SCOPES = ("fwd_bwd", "flatten", "exchange", "update", "guard",
                "step_metrics")
# what an iteration does for the step it dispatches, and for a step it waits
# for
FEED = ["data_wait", "h2d", "step_dispatch"]
ENDS = ["step_sync", "step_readback"]
LEAVES = FEED + ENDS
PREFETCH_DEPTH = 2


def make_cfg(tmp_path, **kw):
    base = dict(
        dnn="mnistnet", dataset="mnist", batch_size=8, nworkers=1, lr=0.05,
        momentum=0.9, weight_decay=1e-4, epochs=1, max_steps=12,
        compressor="auto", density=0.01, compress_warmup_steps=1,
        warmup_epochs=0.0, compute_dtype="float32", output_dir=str(tmp_path),
        log_every=2, eval_every_epochs=0, save_every_epochs=0, seed=0,
        trace="on")
    base.update(kw)
    return TrainConfig(**base)


def read_events(t):
    with open(os.path.join(t.run_dir, "metrics.jsonl")) as f:
        return [json.loads(line) for line in f]


# ------------------------------------------------------------ device scopes

@pytest.fixture(scope="module")
def lowered(tmp_path_factory):
    """The step programs the Trainer builds for one worker with the fused
    EF+select kernel and the flat optimizer (the benchmark cells' build),
    lowered with their locations."""
    t = Trainer(make_cfg(tmp_path_factory.mktemp("scopes"), trace="off",
                         run_id="scopes"))
    t.train(1)
    batch = t._probe_batch
    text = {"sparse": t.ts.sparse_step.lower(t._state, batch).as_text(
                debug_info=True),
            "dense": t.ts.dense_step.lower(t._state, batch).as_text(
                debug_info=True)}
    t.close()
    return text


@pytest.mark.parametrize(
    "program,scope",
    [("sparse", s) for s in SPARSE_SCOPES]
    + [("dense", s) for s in DENSE_SCOPES])
def test_scope_is_in_the_lowered_step(lowered, program, scope):
    """Every phase the trace is read by is a component of some operation's
    location in the lowered program (a transformation may wrap it:
    `vmap(cand_topk)`)."""
    assert re.search(r'[/"(]%s[/")]' % scope, lowered[program]), scope


def test_the_fused_kernel_is_named(lowered):
    assert "ef_select/pallas_call" in lowered["sparse"]


def test_dense_step_has_no_compression_scope(lowered):
    for scope in ("ef_select", "cand_topk", "pack", "scatter"):
        assert not re.search(r'[/"(]%s[/")]' % scope, lowered["dense"])


# --------------------------------------------------------------- host spans

@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """Three steps with tracing on (log_every 2: one log step, at step 2),
    closed; the recording fetched AFTER close."""
    t = Trainer(make_cfg(tmp_path_factory.mktemp("spans"), run_id="spans3"))
    t.train(3)
    t.close()
    events = read_events(t)
    del t
    return tracing.recorded("spans3"), events


def test_recording_survives_close(traced_run):
    rec, _ = traced_run
    assert rec is not None and rec.run_id == "spans3"
    assert len(rec.anchors) >= 2            # the context's, the trajectory's
    assert [s.name for s in rec.spans].count("iteration") == 3


def kids_of(spans, it):
    return sorted((s for s in spans if s.parent == it.span_id),
                  key=lambda s: s.t0_ns)


def test_one_iteration_per_step_with_its_children_in_order(traced_run):
    """The loop keeps one step in flight: an iteration pulls, places and
    dispatches its own step, then waits for, reads and logs the step
    before; the call's last iteration waits for its own step too."""
    rec, _ = traced_run
    spans = list(rec.spans)
    iters = [s for s in spans if s.name == "iteration"]
    assert [s.fields["step"] for s in iters] == [1, 2, 3]
    want = {1: FEED, 2: FEED + ENDS,
            3: FEED + ENDS + ["log_step", "trace_drain"] + ENDS}
    for it in iters:
        kids = kids_of(spans, it)
        assert [k.name for k in kids] == want[it.fields["step"]]
        # inside the parent, one after the other, on a monotone clock
        edges = [it.t0_ns]
        for k in kids:
            edges += [k.t0_ns, k.t1_ns]
        edges.append(it.t1_ns)
        assert edges == sorted(edges)
        ready = kids[0].fields["ready"]
        assert isinstance(ready, int) and 0 <= ready <= PREFETCH_DEPTH
    # `ahead`: the step programs dispatched behind the one waited for
    syncs = [s for s in spans if s.name == "step_sync"]
    assert [s.fields["ahead"] for s in syncs] == [1, 1, 0]
    # every leaf hangs off an iteration, and none holds another span
    leaves = [s for s in spans if s.name in LEAVES]
    assert {s.parent for s in leaves} == {it.span_id for it in iters}
    assert not {s.span_id for s in leaves} & {s.parent for s in spans}


def test_data_wait_carries_the_input_paths_counters(traced_run):
    """``fresh`` on every ``data_wait``; ``assemble_ms`` once the producer
    thread has pulled a batch (by the second iteration it has: the loop
    was handed the first). Both reach the JSONL stream."""
    rec, events = traced_run
    waits = sorted((s for s in rec.spans if s.name == "data_wait"),
                   key=lambda s: s.t0_ns)
    assert len(waits) == 3
    for s in waits:
        assert isinstance(s.fields["fresh"], int) and s.fields["fresh"] >= 0
    for s in waits[1:]:
        assert 0 < s.fields["assemble_ms"] < 60_000
    written = [e for e in events
               if e.get("event") == "span" and e.get("name") == "data_wait"]
    assert len(written) == 3
    assert all("fresh" in e for e in written)
    assert all("assemble_ms" in e for e in written[1:])


@pytest.mark.parametrize("kw,n,ahead", [
    # all steps of a call but its last
    (dict(), 5, [1, 1, 1, 1, 0]),
    # a cadence save: step 2 and step 4 are waited for before the next
    # step is dispatched
    (dict(save_every_steps=2), 5, [1, 0, 1, 0, 0]),
    # a log step at which the policy engine may rebuild the programs
    (dict(policy="adaptive", log_every=3), 5, [1, 1, 0, 1, 0]),
    # a profiler window over steps [2, 3): it opens before step index 2 is
    # dispatched and closes before step index 3 is
    (dict(profile_steps=(2, 3)), 5, [1, 0, 0, 1, 0]),
], ids=["free", "cadence-save", "policy-engine", "profiler-window"])
def test_the_loop_does_not_run_ahead_where_the_host_acts(tmp_path, kw, n,
                                                         ahead):
    t = Trainer(make_cfg(tmp_path, run_id="ahead", **kw))
    t.train(n)
    t.close()
    spans = list(tracing.recorded("ahead").spans)
    syncs = sorted((s for s in spans if s.name == "step_sync"),
                   key=lambda s: s.t0_ns)
    assert [s.fields["ahead"] for s in syncs] == ahead
    iters = [s for s in spans if s.name == "iteration"]
    assert len(iters) == n
    for it in iters:
        names = [k.name for k in kids_of(spans, it)]
        assert names[:3] == FEED
        assert set(names[3:]) <= set(ENDS) | {
            "log_step", "trace_drain", "checkpoint_save", "policy_apply"}
    # one sync and one read-back a step, whoever's iteration holds them
    assert [s.name for s in spans].count("step_readback") == n


def test_a_call_of_one_step_never_runs_ahead(tmp_path):
    t = Trainer(make_cfg(tmp_path, run_id="ones"))
    for _ in range(3):
        t.train(1)
    t.close()
    spans = list(tracing.recorded("ones").spans)
    for it in (s for s in spans if s.name == "iteration"):
        assert [k.name for k in kids_of(spans, it)][:5] == LEAVES
    assert [s.fields["ahead"] for s in spans
            if s.name == "step_sync"] == [0, 0, 0]


def test_iterations_hang_off_the_trajectory_and_construct_is_a_root(
        traced_run):
    rec, events = traced_run
    spans = list(rec.spans)
    traj = [r for r in events if r.get("event") == "span"
            and r.get("name") == "trajectory" and r["ph"] == "B"]
    assert len(traj) == 1 and "wall_ns" in traj[0]
    assert all(s.parent == traj[0]["span_id"] for s in spans
               if s.name == "iteration")
    root = [s for s in spans if s.name == "construct"]
    assert len(root) == 1 and root[0].parent is None
    kids = [s.name for s in spans if s.parent == root[0].span_id]
    assert kids == ["build_step", "build_data", "build_model", "build_step"]
    covered = sum(s.t1_ns - s.t0_ns for s in spans
                  if s.parent == root[0].span_id)
    assert covered <= root[0].t1_ns - root[0].t0_ns


def test_no_span_reaches_the_bus_between_two_log_steps(traced_run):
    """Span records appear in the stream only right after a train record
    (the log step's drain) or at the end (close): never between two log
    steps. Every finished span gets there exactly once."""
    rec, events = traced_run
    kinds = [(r["event"], r.get("ph")) for r in events]
    xs = [i for i, k in enumerate(kinds) if k == ("span", "X")]
    assert len(xs) == len(rec.spans)
    assert [k[0] for k in kinds].count("train") == 1        # step 2
    for i in xs:
        before = [k for k in kinds[:i] if k != ("span", "X")]
        assert before[-1][0] == "train", kinds[:i + 1]
    by_id = {r["span_id"]: r for r in events if r.get("ph") == "X"}
    for s in rec.spans:
        r = by_id[s.span_id]
        assert r["t0_ns"] == s.t0_ns and r["dur_ns"] == s.t1_ns - s.t0_ns


def test_trace_off_records_nothing(tmp_path):
    t = Trainer(make_cfg(tmp_path, trace="off", run_id="untraced"))
    t.train(3)
    t.close()
    assert tracing.recorded("untraced") is None
    events = read_events(t)
    assert not [r for r in events if r["event"] == "span"]
    assert not any("trace_id" in r or "span_id" in r for r in events)


# what the parent commit (PR 23) writes for this configuration and these
# three steps with tracing off (one log step, at step 2): every record's
# kind and keys, in order
PARENT_STREAM = [
    ("config", ["batch_size", "compressor", "dataset", "density", "dnn",
                "event", "lr", "n_params", "nworkers", "schema_version",
                "seq", "total_steps", "ts"]),
    ("train", ["acc", "bytes_sent", "consecutive_skips", "density",
               "density_achieved", "ef_norm", "epoch", "event", "ex_per_s",
               "grad_norm", "io_s", "loss", "lr", "lr_scale", "nonfinite",
               "num_selected", "overlap", "schema_version", "seq", "skipped",
               "step", "step_s", "ts", "wire_format"]),
]


def test_trace_off_stream_is_the_parents(tmp_path):
    t = Trainer(make_cfg(tmp_path, trace="off", run_id="plain"))
    t.train(3)
    t.close()
    got = [(r["event"], sorted(r)) for r in read_events(t)]
    assert got == PARENT_STREAM
    recs = read_events(t)
    assert [r["seq"] for r in recs] == [0, 1]
    assert recs[1]["step"] == 2 and recs[1]["bytes_sent"] == 133072
    assert recs[1]["num_selected"] == 16634.0


# ------------------------------------------------------------- TraceContext

def test_spans_wait_in_memory_until_drained():
    """Nothing is published when a span closes; drain() publishes the
    finished ones in closing order with their own times, and the clock
    pair maps them to wall time."""
    ticks = iter(range(1000, 100000, 10))
    mem = MemoryExporter()
    bus = EventBus([mem])
    tc = TraceContext(bus, trace_id="t-mem", run_id="mem",
                      clock_ns=lambda: next(ticks),
                      wall_ns=lambda: 5_000_000)
    with tc.span("outer", step_num=7):
        with tc.span("inner", ready=1):
            pass
    assert mem.records == []
    assert tc.drain() == 2 and tc.drain() == 0
    inner, outer = mem.records
    assert (inner["name"], outer["name"]) == ("inner", "outer")
    assert inner["parent_span"] == outer["span_id"]
    assert outer["step"] == 7 and inner["ready"] == 1
    assert outer["t0_ns"] < inner["t0_ns"]
    assert inner["t0_ns"] + inner["dur_ns"] <= outer["t0_ns"] + outer["dur_ns"]
    rec = tracing.recorded("mem")
    assert rec is tc.recording and [s.name for s in rec.spans] == [
        "inner", "outer"]
    # the pair taken at construction: perf 1000 <-> wall 5 000 000
    assert rec.wall_ns(1000) == 5_000_000
    assert rec.wall_ns(outer["t0_ns"]) == 5_000_000 + outer["t0_ns"] - 1000


def test_a_full_pending_list_drains_itself(monkeypatch):
    monkeypatch.setattr(tracing, "PENDING_SPANS", 4)
    mem = MemoryExporter()
    tc = TraceContext(EventBus([mem]), trace_id="t-full")
    for _ in range(9):
        with tc.span("s"):
            pass
    assert len(mem.records) == 8 and tc.drain() == 1


def test_spans_open_the_annotations_they_were_given():
    opened = []

    class Note:
        def __init__(self, name, **kw):
            self.what = (name, kw)

        def __enter__(self):
            opened.append(("enter",) + self.what)

        def __exit__(self, *exc):
            opened.append(("exit",) + self.what)

    tc = TraceContext(EventBus([MemoryExporter()]), trace_id="t-note",
                      annotate=Note, step_annotate=Note)
    with tc.span("iteration", step_num=3):
        with tc.span("h2d"):
            pass
    assert opened == [("enter", "iteration", {"step_num": 3}),
                      ("enter", "h2d", {}), ("exit", "h2d", {}),
                      ("exit", "iteration", {"step_num": 3})]


def test_chrome_trace_places_spans_by_their_own_clock():
    """The renderer maps `t0_ns` through the trajectory's clock pair, not
    through the record's publish time."""
    recs = [
        {"event": "span", "name": "trajectory", "span_id": "s1", "ph": "B",
         "t0_ns": 1_000_000, "wall_ns": 2_000_000_000_000, "ts": 2000.0},
        {"event": "train", "step": 2, "ts": 2000.5},
        {"event": "span", "name": "h2d", "span_id": "s2", "ph": "X",
         "parent_span": "s1", "t0_ns": 101_000_000, "dur_ns": 3_000_000,
         "ts": 2000.9},
    ]
    trace = tracing.build_chrome_trace(recs)
    evs = {e["name"]: e for e in trace["traceEvents"] if e.get("ph") != "M"}
    assert evs["trajectory"]["ts"] == 0.0
    assert evs["h2d"]["ts"] == pytest.approx(100_000.0)     # 100 ms later
    assert evs["h2d"]["dur"] == pytest.approx(3_000.0)
    assert evs["train"]["ph"] == "i" and evs["train"]["ts"] == pytest.approx(
        500_000.0)
    tracks = {e["args"]["name"] for e in trace["traceEvents"]
              if e.get("name") == "thread_name"}
    assert tracks == {"host phases", "events"}
