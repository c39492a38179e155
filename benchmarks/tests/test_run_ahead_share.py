"""`layer_metrics/run_ahead_share.py` (PR 28): on made-up spans whose share
can be worked out by eye, on the recording of PR 24's program under
`benchmarks/testdata/` (the parent's: its `step_sync` spans have no `ahead`
field), and on a traced run of the tiny cell on the CPU."""

import argparse
import json
import os
from collections import namedtuple

import pytest

from benchmarks import harness, run, span_reduce as sr, trace_reduce
from gaussiank_sgd_tpu.telemetry import tracing

TESTDATA = os.path.join(harness.HERE, "testdata")
MS = 1_000_000          # nanoseconds
Rec = namedtuple("Rec", "spans anchors trace_id")


@pytest.fixture()
def reader():
    return harness.load_layer_metric(
        os.path.join(harness.HERE, "layer_metrics"), "run_ahead_share")


def span(name, sid, parent, t0, t1, **fields):
    return tracing.Span(name, sid, parent, t0, t1, "host", fields)


def a_block(ahead_of_each, t0_ms=0):
    """One `train(n)` call as the loop records it: iteration k dispatches
    step k and waits for the step before; the last waits for its own too."""
    spans, t = [], t0_ms * MS
    for k, ahead in enumerate(ahead_of_each):
        it = f"i{t0_ms}_{k}"
        spans.append(span("step_dispatch", it + "d", it, t, t + MS))
        spans.append(span("step_sync", it + "s", it, t + MS, t + 9 * MS,
                          **({} if ahead is None else {"ahead": ahead})))
        spans.append(span("iteration", it, "t", t, t + 10 * MS, step=k + 1))
        t += 10 * MS
    return spans


def test_the_share_of_syncs_with_a_step_queued_behind_them(reader,
                                                           monkeypatch):
    """Two counted blocks of four steps, three of each run ahead: 6 of 8.
    A third call lies outside the blocks (a lead-in) and counts for
    nothing, though none of its steps ran ahead."""
    spans = (a_block([1, 1, 1, 0]) + a_block([1, 1, 1, 0], t0_ms=100)
             + a_block([0, 0], t0_ms=60))
    monkeypatch.setattr(sr, "spans_of", lambda run_id: Rec(spans, [], "t"))
    blocks = {"sparse": [{"t0": 0.0, "t1": 0.040}, {"t0": 0.100, "t1": 0.140}]}
    assert reader.read({"blocks": blocks}) == pytest.approx(75.0)
    # a loop that waits for every step reads 0, not nothing
    monkeypatch.setattr(sr, "spans_of", lambda run_id: Rec(
        a_block([0, 0, 0, 0]), [], "t"))
    assert reader.read({"blocks": blocks}) == 0.0


def test_nothing_to_read_on_the_parents_recording(reader, monkeypatch):
    """A program from before PR 28 records `step_sync` without the field:
    made-up spans, then PR 24's own recording from the chip. No recording
    at all (tracing off) reads nothing either, and nothing raises."""
    blocks = {"sparse": [{"t0": 0.0, "t1": 0.040}]}
    monkeypatch.setattr(sr, "spans_of", lambda run_id: Rec(
        a_block([None, None, None, None]), [], "t"))
    assert reader.read({"blocks": blocks}) is None
    with open(os.path.join(TESTDATA, "tiny_spans_4steps.spans.json")) as f:
        saved = json.load(f)
    with open(os.path.join(TESTDATA, "tiny_spans_4steps.block.json")) as f:
        block = json.load(f)
    rec = tracing.Recording(saved["run_id"], saved["trace_id"])
    rec.spans.extend(tracing.Span(*s) for s in saved["spans"])
    assert sum(s.name == "step_sync"
               for s in sr.in_block(list(rec.spans), block)) == 4
    monkeypatch.setattr(sr, "spans_of", lambda run_id: rec)
    assert reader.read({"blocks": {"sparse": [block]}}) is None
    monkeypatch.setattr(sr, "spans_of", lambda run_id: None)
    assert reader.read({"blocks": blocks}) is None


def test_the_real_benchmark_lists_it_last_in_all_three_cells():
    bench = harness.load_benchmark()
    entry = bench["per_layer"][-1]
    assert entry == {
        "name": "run_ahead_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "host loop",
        "moves": "examples_per_s",
        "workloads": ["vgg16_dp1", "resnet50_dp1", "vgg16_dp4"]}
    assert [m["name"] for m in bench["per_layer"]].count(entry["name"]) == 1


def test_a_traced_run_on_the_cpu_reports_it(tiny_root, capsys, monkeypatch):
    """The tiny cell's blocks are `Trainer.train(n)` calls of this
    checkout's program: every step of a block but its last runs ahead."""
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    if "run_ahead_share" not in {m["name"] for m in bench["per_layer"]}:
        entry = dict(harness.load_benchmark()["per_layer"][-1])
        entry.pop("workloads")
        bench["per_layer"].append(entry)
        with open(path, "w") as f:
            json.dump(bench, f)
    monkeypatch.setattr(
        trace_reduce, "reduce_run", lambda traced, run: {
            "arms": {}, "busy_s": 0.0, "window_s": 1.0,
            "breakdown": {"device_ops": [], "idle_gaps": []}})
    cell = harness.load_cell("tiny_dp1", root=tiny_root)
    args = argparse.Namespace(workload="tiny_dp1", seed=11, seconds=1.0,
                              trace=1)
    out_dir = harness.make_out_dir()
    try:
        rc = run._run(args, cell, {"bf16_flops_per_s": 1.0,
                                   "hbm_bytes_per_s": 1.0},
                      harness.CompileLog(), out_dir)
    finally:
        harness.remove_out_dir(out_dir)
    out = capsys.readouterr().out
    result = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True, out
    share = result["metrics"]["run_ahead_share"]
    assert share["unit"] == "%"
    blocks = [int(line.split()[3]) for line in out.splitlines()
              if line.startswith("block ") and " sparse " in line]
    steps = sum(blocks)
    assert share["value"] == pytest.approx(
        100.0 * (steps - len(blocks)) / steps)
