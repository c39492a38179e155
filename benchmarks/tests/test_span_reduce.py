"""`span_reduce.py`: on made-up spans and operations whose numbers can be
worked out by eye, on a small trace recorded WITH the program's scopes and
spans on a TPU v5 lite chip by `record_span_trace.py` (PR 24), kept under
`benchmarks/testdata/`, on a traced run of the tiny cell on the CPU, and on
an untraced run, where every reader has nothing to read."""

import argparse
import json
import os
import shutil
from collections import namedtuple

import pytest

from benchmarks import harness, run, span_reduce as sr, trace_reduce
from gaussiank_sgd_tpu.telemetry import tracing

TESTDATA = os.path.join(harness.HERE, "testdata")
NAME = "tiny_spans_4steps"
MS = 1_000_000          # nanoseconds
NEW_METRICS = [
    "loop_data_wait_ms", "input_ready_batches", "h2d_ms", "dispatch_ms",
    "step_sync_ms", "step_readback_ms", "log_step_ms", "loop_self_ms",
    "idle_unnamed_pct", "fwd_bwd_ms", "cand_topk_ms", "pack_scatter_ms",
    "update_ms", "guard_ms", "step_metrics_ms", "construct_data_s",
    "construct_program_s"]
HOST_METRICS = NEW_METRICS[:8] + NEW_METRICS[-2:]

Rec = namedtuple("Rec", "spans anchors trace_id")


def span(name, sid, parent, t0, t1, **fields):
    return tracing.Span(name, sid, parent, t0, t1, "host", fields)


def since_start(rec, start_unix_ns):
    """A span's `perf_counter_ns` in nanoseconds since the session
    started, through the recording's clock pair."""
    return lambda perf_ns: rec.wall_ns(perf_ns) - start_unix_ns


# ------------------------------------------------------------- by the eye

@pytest.mark.parametrize("tf_op,scope", [
    ("jit(sparse_step_fn)/vmap(cand_topk)/top_k:", "cand_topk"),
    ("jit(sparse_step_fn)/ef_select/ef_select/pallas_call:", "ef_select"),
    # the innermost listed name wins over a compressor's outer ef_select
    ("jit(sparse_step_fn)/ef_select/vmap(pack)/gather:", "pack"),
    ("jit(sparse_step_fn)/pack/scatter/scatter:", "scatter"),
    # inside fwd_bwd a module may be called anything
    ("jit(f)/fwd_bwd/transpose(jvp(VGG))/update/conv_general_dilated:",
     "fwd_bwd"),
    ("jit(sparse_step_fn)/jit(_threefry_fold_in)/slice:", None),
    ("state.params['Dense_1']['kernel']:", None),
    ("jit(f)/repack/add:", None),        # a name is a whole component
    ("", None),
])
def test_scope_of_an_op_name(tf_op, scope):
    assert sr.scope_of(tf_op) == scope


def test_self_time_leaves_out_what_runs_inside():
    """A 10 us `while` holding two body operations of 3 us and 4 us (the
    second holding 1 us of its own child), then a 2 us operation apart."""
    ops = [("while", 0, 10_000, "w"), ("body.1", 1_000, 3_000, "a"),
           ("body.2", 5_000, 4_000, "b"), ("inner", 6_000, 1_000, "c"),
           ("after", 12_000, 2_000, "d")]
    got = {op[0]: ps for op, ps in sr.self_times(ops)}
    assert got == {"while": 3_000, "body.1": 3_000, "body.2": 3_000,
                   "inner": 1_000, "after": 2_000}
    assert sum(got.values()) == 12_000      # the union: nothing counted twice


def test_host_means_and_the_self_time_arithmetic():
    """Two iterations of 10 ms and 14 ms in one block; the second is a log
    step. A third iteration lies outside the block and counts for
    nothing."""
    spans = [
        span("data_wait", "a1", "i1", 0 * MS, 1 * MS, ready=0),
        span("h2d", "a2", "i1", 1 * MS, 3 * MS),
        span("step_dispatch", "a3", "i1", 3 * MS, 4 * MS),
        span("step_sync", "a4", "i1", 4 * MS, 9 * MS),
        span("iteration", "i1", "t", 0, 10 * MS, step=1),
        span("data_wait", "b1", "i2", 10 * MS, 10 * MS, ready=2),
        span("h2d", "b2", "i2", 10 * MS, 12 * MS),
        span("step_dispatch", "b3", "i2", 12 * MS, 13 * MS),
        span("step_sync", "b4", "i2", 13 * MS, 18 * MS),
        span("log_step", "b5", "i2", 19 * MS, 23 * MS),
        span("iteration", "i2", "t", 10 * MS, 24 * MS, step=2),
        span("h2d", "c2", "i3", 40 * MS, 49 * MS),
        span("iteration", "i3", "t", 40 * MS, 50 * MS, step=3),
    ]
    block = {"t0": 0.0, "t1": 0.030}
    got = sr.reduce_host(spans, [block])
    assert got["iterations"] == 2
    per = got["per_iteration_s"]
    assert per["data_wait"] == pytest.approx(0.5e-3)
    assert per["h2d"] == pytest.approx(2e-3)
    assert per["step_sync"] == pytest.approx(5e-3)
    assert per["log_step"] == pytest.approx(2e-3)           # a half of 4 ms
    assert got["per_occurrence_s"]["log_step"] == pytest.approx(4e-3)
    assert got["ready_mean"] == 1.0
    assert got["iteration_s"] == pytest.approx(12e-3)
    # 24 ms of iterations less 9 + 12 ms of children: 1.5 ms each
    assert got["self_s"] == pytest.approx(1.5e-3)
    assert got["self_s"] + sum(per.values()) == pytest.approx(
        got["iteration_s"])
    assert sr.reduce_host(spans, [{"t0": 0.060, "t1": 0.070}]) is None


def test_construction_spans_add_up_over_the_trainers():
    one = Rec([span("build_step", "s2", "s1", 0, 1 * MS),
               span("build_data", "s3", "s1", 1 * MS, 4 * MS),
               span("build_model", "s4", "s1", 4 * MS, 5 * MS),
               span("build_step", "s5", "s1", 5 * MS, 9 * MS),
               span("construct", "s1", None, 0, 10 * MS),
               span("h2d", "s9", "s8", 20 * MS, 30 * MS)], [], "t")
    got = sr.reduce_setup([one, one])
    assert got == pytest.approx({"build_step": 10e-3, "build_data": 6e-3,
                                 "build_model": 2e-3})
    assert sr.reduce_setup([Rec([], [], "t")]) is None


def test_idle_time_is_named_by_the_leaf_span_the_host_was_in():
    """A 20 ms block; the device is busy 2-8 ms and 12-18 ms. The host:
    data_wait 0-1, h2d 1-1.5, dispatch 1.5-3, sync 3-9, readback 9-10,
    nothing 10-11, dispatch 11-13, sync 13-19, nothing to the end. Idle is
    0-2, 8-12 and 18-20 ms."""
    spans = [
        span("data_wait", "a1", "i1", 0, 1 * MS),
        span("h2d", "a2", "i1", 1 * MS, 1.5 * MS),
        span("step_dispatch", "a3", "i1", 1.5 * MS, 3 * MS),
        span("step_sync", "a4", "i1", 3 * MS, 9 * MS),
        span("step_readback", "a5", "i1", 9 * MS, 10 * MS),
        span("iteration", "i1", "t", 0, 10.5 * MS),
        span("step_dispatch", "b3", "i2", 11 * MS, 13 * MS),
        span("step_sync", "b4", "i2", 13 * MS, 19 * MS),
        span("iteration", "i2", "t", 10.5 * MS, 19.5 * MS),
    ]
    # the session started 7 s before the spans' clock read 0
    busy = [(7_000 * MS + 2 * MS, 7_000 * MS + 8 * MS),
            (7_000 * MS + 12 * MS, 7_000 * MS + 18 * MS)]
    got = sr.name_idle(busy, spans, lambda perf_ns: perf_ns + 7_000 * MS,
                       {"t0": 0.0, "t1": 0.020})
    assert got["idle_s"] == pytest.approx(8e-3)
    by = got["by_span_s"]
    assert by["data_wait"] == pytest.approx(1e-3)
    assert by["h2d"] == pytest.approx(0.5e-3)
    assert by["step_dispatch"] == pytest.approx(0.5e-3 + 1e-3)
    assert by["step_sync"] == pytest.approx(1e-3 + 1e-3)
    assert by["step_readback"] == pytest.approx(1e-3)
    # 10-11 ms between the spans, and 19-20 ms after the last: the
    # iterations themselves are no leaves
    assert by["unnamed"] == pytest.approx(2e-3)
    assert sum(by.values()) == pytest.approx(got["idle_s"])


def test_the_devices_clock_is_anchored_on_the_blocks_own_steps():
    """Two steps. By the nominal mapping the programs start 0.2 ms BEFORE
    their dispatch opens (the device plane is early), and end 1.5 and
    1.1 ms before their sync closes: the plane has to move by 0.2 to
    1.1 ms, and is moved by the most it can."""
    spans = [
        span("step_dispatch", "a3", "i1", 10 * MS, 11 * MS),
        span("step_sync", "a4", "i1", 11 * MS, 16.3 * MS),
        span("iteration", "i1", "t", 9 * MS, 17 * MS),
        span("step_dispatch", "b3", "i2", 20 * MS, 21 * MS),
        span("step_sync", "b4", "i2", 21 * MS, 25.9 * MS),
        span("iteration", "i2", "t", 19 * MS, 27 * MS),
    ]
    programs = [(9.8 * MS, 14.8 * MS), (19.8 * MS, 24.8 * MS)]
    got = sr.anchor_clock(programs, spans, lambda perf_ns: perf_ns)
    assert got["lo_ns"] == pytest.approx(0.2 * MS)
    assert got["hi_ns"] == pytest.approx(1.1 * MS)
    # the latest the plane can sit: the second program then ends as its
    # sync closes, the first 0.4 ms before
    assert got["shift_ns"] == pytest.approx(1.1 * MS)
    assert got["lo_spread_ns"] == pytest.approx(0.0)
    assert got["hi_spread_ns"] == pytest.approx(0.4 * MS)
    # moved, the programs start 10.9 and 20.9 ms: both 0.1 ms before their
    # sync opens, so no sync waits for its program to start
    assert got["sync_before_program_ns"] == pytest.approx(0.0)
    assert got["sync_after_program_ns"] == pytest.approx(0.2 * MS)
    assert sr.anchor_clock(programs[:1], spans, lambda t: t) is None


# ------------------------------------------------------- the recorded trace

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """`testdata/tiny_spans_4steps.*`: four sparse steps of the tests' tiny
    cell on one TPU v5 lite chip with the program's tracing on
    (`record_span_trace.py`, PR 24): the device-only trace, the block's
    host timings, the recorded spans of the construction and the block."""
    # kept as `.xspace.pb`: the old recording's test reduces every
    # `*.xplane.pb` under testdata/ as one trace
    tdir = str(tmp_path_factory.mktemp("spans_trace"))
    shutil.copy(os.path.join(TESTDATA, NAME + ".xspace.pb"),
                os.path.join(tdir, NAME + ".xplane.pb"))
    with open(os.path.join(TESTDATA, NAME + ".block.json")) as f:
        block = json.load(f)
    with open(os.path.join(TESTDATA, NAME + ".spans.json")) as f:
        saved = json.load(f)
    rec = tracing.Recording(saved["run_id"], saved["trace_id"])
    rec.anchors = [tuple(a) for a in saved["anchors"]]
    rec.spans.extend(tracing.Span(*s) for s in saved["spans"])
    return tdir, block, rec


def test_the_recorded_files_raw_decoding_agrees_with_profile_data(recorded):
    """The wire-format decoder against `jax.profiler.ProfileData` on the
    same file: the same events with the same names, starts and durations
    (it adds what ProfileData leaves out, the metadata's `tf_op`)."""
    tdir, _, _ = recorded
    path = trace_reduce.find_xplanes(tdir)[0]
    theirs = trace_reduce.read_planes(path)[0]
    mine = [p for p in sr.read_xspace(path)
            if p["name"] == "/device:TPU:0"][0]["lines"]
    assert len(mine[sr.OPS_LINE]) == len(theirs["ops"]) == 844
    assert len(mine[sr.MODULES_LINE]) == len(theirs["modules"]) == 13
    for (name, ps, dur, _), (n2, ns, d2) in zip(mine[sr.OPS_LINE],
                                                theirs["ops"]):
        assert name == n2 and ps // 1000 == ns and abs(dur / 1e3 - d2) < 1
    task = [p for p in sr.read_xspace(path) if p["name"] == sr.TASK_PLANE]
    assert task[0]["stats"][sr.START_STAT] == 1790560099862505582


def test_the_recorded_trace_by_scope(recorded):
    """By hand, from a listing of the device plane's "XLA Ops" with each
    event's metadata stat `tf_op` (an independent decoder, PR 24): 844
    events, none inside another, 248 248 906 ps in all. Per scope, summed
    over the four steps, in picoseconds:

      fwd_bwd 159 103 750 (228 events)   flatten   14 402 032 (24)
      ef_select 3 520 078 (8: the kernel `ef_select.1`, 546-547 ns a step,
                and the controller's `reduce_min`)
      cand_topk 19 349 922 (12: `vmap(cand_topk)/abs`, `top_k`)
      pack 4 318 828 (20)                scatter    6 413 124 (24)
      update 21 237 420 (68)             guard      5 928 592 (72)
      step_metrics 3 671 954 (8)         no scope  10 303 206 (380: copies,
                parameters, the rng's fold-in, the log line's scalar
                programs)
    No `exchange`: one chip, the collectives over a one-device axis are
    gone from the compiled program."""
    tdir, block, _ = recorded
    dev = sr.reduce_device(tdir, block["steps"])
    assert dev["chips"] == 1 and dev["scoped"]
    want_ps = {"fwd_bwd": 159_103_750, "flatten": 14_402_032,
               "ef_select": 3_520_078, "cand_topk": 19_349_922,
               "pack": 4_318_828, "scatter": 6_413_124,
               "update": 21_237_420, "guard": 5_928_592,
               "step_metrics": 3_671_954, "": 10_303_206}
    got = dev["scope_s_per_step"]
    assert set(got) == set(want_ps)
    for scope, ps in want_ps.items():
        assert got[scope] == pytest.approx(ps / 4 / 1e12, rel=1e-9), scope
    assert sum(got.values()) * 4 == pytest.approx(248_248_906e-12)
    # the four step programs, nanoseconds since the session started
    assert [(a // 1000, (b - a) // 1000) for a, b in dev["programs_ps"]] == [
        (51_836_128, 79_130), (55_535_905, 78_833), (58_919_347, 78_836),
        (62_461_463, 78_863)]
    assert dev["start_unix_ns"] == 1790560099862505582
    assert sum(b - a for a, b in dev["busy_ps"]) == 248_248_906
    # what has no scope, by XLA's opcode, seconds per step: fusions that
    # XLA built without an `op_name` 1.385 us, copies 0.871 us
    top = dict(map(tuple, dev["unscoped_top"]))
    assert list(top)[:4] == ["fusion", "copy", "copy-start", "copy-done"]
    assert top["fusion"] == pytest.approx(1.3854495e-6)
    assert top["copy"] == pytest.approx(8.71289e-7)


def test_the_recorded_spans_and_the_clock(recorded):
    """By hand, from the listing of `tiny_spans_4steps.spans.json`: four
    iterations (steps 23-26) of 3 949 120, 3 432 360, 3 528 680 and
    3 502 550 ns, each with its five leaves, the prefetch queue full (2)
    every time; after the loop the quiet `log_step` (7 790 209 ns) and the
    `trace_drain` (1 891 410 ns) hang off the trajectory.

    The clock: the block opens 50 603 268 ns after `profile_start_time`
    through the recording's second clock pair. By that mapping the four
    step programs start 78 400, 90 003, 117 580 and 128 674 ns BEFORE
    their `step_dispatch` spans open, and end 1 663 720, 1 473 229,
    1 612 414 and 1 609 991 ns before their `step_sync` spans close: the
    device-only trace's plane is early and has to move by 128.7 to
    1 473.2 us, and is moved by the latter."""
    tdir, block, rec = recorded
    spans = list(rec.spans)
    host = sr.reduce_host(spans, [block])
    assert host["iterations"] == 4 and host["ready_mean"] == 2.0
    assert host["iteration_s"] == pytest.approx(
        (3_949_120 + 3_432_360 + 3_528_680 + 3_502_550) / 4e9)
    per = host["per_iteration_s"]
    assert set(per) == {"data_wait", "h2d", "step_dispatch", "step_sync",
                        "step_readback"}
    assert per["data_wait"] == pytest.approx(
        (39_340 + 40_880 + 52_110 + 38_640) / 4e9)
    assert per["step_readback"] == pytest.approx(
        (986_039 + 928_650 + 951_490 + 934_910) / 4e9)
    assert host["self_s"] + sum(per.values()) == pytest.approx(
        host["iteration_s"])
    assert sr.reduce_setup([rec]) == pytest.approx(
        {"build_data": 2_626_990e-9, "build_model": 691_276_346e-9,
         "build_step": (40_720 + 154_866_716) * 1e-9})

    dev = sr.reduce_device(tdir, block["steps"])
    to_ns = since_start(rec, dev["start_unix_ns"])
    assert to_ns(int(block["t0"] * 1e9)) == 50_603_268
    progs = [(a / 1e3, b / 1e3) for a, b in dev["programs_ps"]]
    clock = sr.anchor_clock(progs, sr.in_block(spans, block), to_ns)
    assert clock["lo_ns"] == pytest.approx(128_674, abs=1)
    assert clock["hi_ns"] == pytest.approx(1_473_229, abs=1)
    assert clock["lo_spread_ns"] == pytest.approx(128_674 - 78_400, abs=1)
    assert clock["shift_ns"] == clock["hi_ns"]
    # moved by 1 473 229 ns the programs start 261 659, 348 097, 338 269
    # and 321 385 ns after their step_sync spans open, and the syncs close
    # 190 491, 0, 139 185 and 136 762 ns after the programs' ends
    assert clock["sync_before_program_ns"] == pytest.approx(317_352.5, abs=1)
    assert clock["sync_after_program_ns"] == pytest.approx(116_609.5, abs=1)


def test_the_recorded_blocks_idle_time_by_span(recorded):
    """The block lasts 24 480 198 ns and the device works 248 249 ns of
    it. While the host waits for data (170 970 ns) and reads the scalars
    back (3 801 089 ns) the device does nothing, so all of both is idle
    time under their names; `log_step` is idle but for the scalar programs
    of its log line. What no leaf covers (the gaps between the leaves,
    the loop's bookkeeping) is `unnamed`, and the parts add up."""
    tdir, block, rec = recorded
    spans = list(rec.spans)
    dev = sr.reduce_device(tdir, block["steps"])
    busy = [(1_473_229 + a / 1e3, 1_473_229 + b / 1e3)
            for a, b in dev["busy_ps"]]
    got = sr.name_idle(busy, spans,
                       since_start(rec, dev["start_unix_ns"]), block)
    assert got["idle_s"] == pytest.approx((24_480_198 - 248_249) / 1e9,
                                          abs=2e-9)
    by = got["by_span_s"]
    assert by["data_wait"] == pytest.approx(170_970e-9)
    assert by["step_readback"] == pytest.approx(3_801_089e-9)
    assert 7_780_000e-9 < by["log_step"] < 7_790_209e-9
    assert by["trace_drain"] == pytest.approx(1_891_410e-9)
    assert sum(by.values()) == pytest.approx(got["idle_s"])
    # the four programs (79 us each, their operations 247 us together) now
    # run inside their step_sync spans; the dispatch spans are all idle
    assert by["step_dispatch"] == pytest.approx(
        (1_112_420 + 1_017_009 + 1_000_660 + 1_008_520) / 1e9)
    assert by["step_sync"] == pytest.approx(
        (531_280 + 426_930 + 556_290 + 537_010 - 247_000) / 1e9, abs=3e-6)
    assert 0 < by["unnamed"] / got["idle_s"] < 0.06


# ------------------------------------------------------------------ a run

def with_new_metrics(root):
    """The tiny root with this PR's seventeen entries appended to its
    BENCHMARK.json, as the real one has them (the readers are found in
    `benchmarks/layer_metrics/`)."""
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        real = {m["name"]: m for m in json.load(f)["per_layer"]}
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    have = {m["name"] for m in bench["per_layer"]}
    for name in NEW_METRICS:
        if name not in have:
            entry = dict(real[name])
            entry.pop("workloads")
            bench["per_layer"].append(entry)
    with open(path, "w") as f:
        json.dump(bench, f)


def test_the_real_benchmark_lists_the_new_metrics_last():
    bench = harness.load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    # then PR 26's one of the exchange, read in the four-chip cell alone
    assert names[-18:-1] == NEW_METRICS
    assert names[-1] == "exchange_ms"
    for m in bench["per_layer"][-18:]:
        assert m["workloads"] == (
            ["vgg16_dp4"] if m["layer"] == "exchange"
            else ["vgg16_dp1", "resnet50_dp1", "vgg16_dp4"])
        assert os.path.exists(os.path.join(harness.HERE, "layer_metrics",
                                           m["name"] + ".py"))


def test_a_traced_run_on_the_cpu_reports_the_host_metrics(
        tiny_root, capsys, monkeypatch):
    """A `--trace 1` run of the tiny cell, driven as `run.py` drives it.
    The CPU has no device plane, so the old reduction is stood in for and
    the device readers find nothing; the spans need no profiler."""
    with_new_metrics(tiny_root)
    monkeypatch.setattr(
        trace_reduce, "reduce_run", lambda traced, run: {
            "arms": {}, "busy_s": 0.0, "window_s": 1.0,
            "breakdown": {"device_ops": [], "idle_gaps": []}})
    cell = harness.load_cell("tiny_dp1", root=tiny_root)
    args = argparse.Namespace(workload="tiny_dp1", seed=7, seconds=1.0,
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
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(HOST_METRICS) <= set(m), sorted(m)
    assert not set(NEW_METRICS) - set(HOST_METRICS) & set(m)
    assert 0 <= m["input_ready_batches"] <= 2
    leaves = (m["loop_data_wait_ms"] + m["h2d_ms"] + m["dispatch_ms"]
              + m["step_sync_ms"] + m["step_readback_ms"])
    assert 0 < leaves and 0 <= m["loop_self_ms"] < leaves
    assert m["log_step_ms"] > 0
    # the outside twin: the same wait, seen from the timed iterator inside
    # the span
    assert m["data_wait_ms"] <= m["loop_data_wait_ms"] <= (
        m["data_wait_ms"] + 0.5)
    assert m["construct_data_s"] > 0 and m["construct_program_s"] > 0
    assert "spans sparse:" in out and "spans construction" in out


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_finds_nothing_in_an_untraced_run(name, monkeypatch):
    """Tracing off, or a program from before PR 24: no recording, and the
    reader returns None without raising. A recording left in the process
    by an earlier run, which holds no iteration of this run's blocks, is
    not read either."""
    reader = harness.load_layer_metric(
        os.path.join(harness.HERE, "layer_metrics"), name)
    blocks = {"sparse": [{"t0": 10.0, "t1": 15.0, "steps": 3,
                          "traced": False}], "dense": []}
    monkeypatch.setattr(sr, "spans_of", lambda run_id: None)
    assert reader.read({"blocks": blocks, "trace": None}) is None
    stale = Rec([span("iteration", "i1", "t", 0, 10 * MS, step=1)], [], "t")
    monkeypatch.setattr(sr, "spans_of", lambda run_id: stale)
    assert reader.read({"blocks": blocks, "trace": None}) is None
