"""The reduction from a trace to numbers: on a made-up plane whose numbers
can be worked out by eye, and on a small trace recorded on a TPU v5 lite
chip by `record_trace.py`, kept under `benchmarks/testdata/`."""

import json
import os

import pytest

from benchmarks import harness, trace_reduce as tr

TESTDATA = os.path.join(harness.HERE, "testdata")
MS = 1_000_000          # nanoseconds


def test_union_of_intervals():
    assert tr.union_ns([]) == 0
    assert tr.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert tr.union_ns([(5, 6), (0, 100), (50, 120)]) == 120


def test_short_names_and_kinds():
    hlo = ("%all-reduce.3 = f32[59948]{0:T(1024)} all-reduce(f32[59948]{0} "
           "%fusion.9), replica_groups={{0,1,2,3}}")
    assert tr.short_name(hlo) == "all-reduce.3"
    assert tr.is_collective(hlo) and not tr.is_kernel(hlo)
    call = ("%sparse_step_fn.1 = (f32[1,15073280]{1,0}) custom-call(f32[1,"
            "15073280]{1,0} %p), custom_call_target=\"tpu_custom_call\"")
    assert tr.short_name(call) == "sparse_step_fn.1 custom-call"
    assert tr.is_kernel(call) and not tr.is_collective(call)
    assert not tr.is_kernel("%fusion.2 = bf16[8]{0} fusion(bf16[8]{0} %x)")


def test_a_made_up_plane_by_eye():
    """Two step programs of 10 ms with a 5 ms gap, a stray scalar program in
    the gap; in each step 4 ms + 3 ms of ordinary operations (1 ms of the
    second overlaps the first), a 2 ms custom call and a 0.5 ms all-reduce."""
    def step(t0):
        return [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %a)", t0, 4 * MS),
                ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %b)", t0 + 3 * MS,
                 3 * MS),
                ("%k.1 = f32[8]{0} custom-call(f32[8]{0} %c), custom_call_"
                 "target=\"tpu_custom_call\"", t0 + 6 * MS, 2 * MS),
                ("%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %d)",
                 t0 + 9 * MS, MS // 2)]
    plane = {"name": "/device:TPU:0",
             "ops": step(0) + step(15 * MS),
             "modules": [("jit_sparse_step_fn(1)", 0, 10 * MS),
                         ("jit_convert_element_type(2)", 12 * MS, 1000),
                         ("jit_sparse_step_fn(1)", 15 * MS, 10 * MS)]}
    got = tr.reduce_plane(plane)
    # per step: [0,6) u [6,8) u [9,9.5) = 8.5 ms busy
    assert got["busy_s"] == pytest.approx(2 * 8.5e-3)
    assert got["kernel_s"] == pytest.approx(2 * 2e-3)
    assert got["collective_s"] == pytest.approx(2 * 0.5e-3)
    assert got["modules"] == 2 and got["gaps_s"] == [pytest.approx(5e-3)]
    assert got["in_step_idle_s"] == pytest.approx(2 * 1.5e-3)
    assert got["by_name"]["k.1 custom-call"] == pytest.approx(4e-3)


def test_idle_gaps_are_named_by_the_hosts_loop(monkeypatch):
    plane = {"busy_s": 0.02, "kernel_s": 0.002, "collective_s": 0.0,
             "by_name": {"fusion.1": 0.02}, "modules": 3, "module_s": 0.021,
             "kernel_hlo": [],
             "gaps_s": [0.010, 0.004], "in_step_idle_s": 0.001}
    monkeypatch.setattr(tr, "find_xplanes", lambda d: ["x"])
    monkeypatch.setattr(tr, "read_planes", lambda p, prefix: [plane])
    monkeypatch.setattr(tr, "reduce_plane", lambda p: p)
    # three steps, the first is global step 18 -> 19, so the second gap
    # follows step 20, a log step; the loop waited 7 ms for its second batch
    block = {"steps": 3, "t0": 1.0, "t1": 1.05, "first_step": 18,
             "wait_s": [0.0, 0.007, 0.001]}
    got = tr.reduce_block("dir", block, log_every=10)
    assert got["idle_named"]["data_wait"] == pytest.approx(0.007 + 0.001)
    assert got["idle_named"]["loop_other"] == pytest.approx(0.003)
    assert got["idle_named"]["log_step"] == pytest.approx(0.003)
    assert got["idle_named"]["in_step"] == pytest.approx(0.001)
    assert got["busy_s_per_step"] == pytest.approx(0.02 / 3)
    assert got["window_s"] == pytest.approx(0.05)


def test_the_recorded_trace_against_numbers_worked_out_by_hand():
    """`testdata/tiny_sparse_4steps.xplane.pb`: four sparse steps of the
    tests' tiny cell on one TPU v5 lite chip (`record_trace.py`, PR 23).

    By hand, from a listing of the device plane's two lines:
      * "XLA Modules" holds 13 events, four of them `jit_sparse_step_fn` of
        79 122 + 78 642 + 79 094 + 78 839 ns; the other nine are the scalar
        programs of the log line after step 30 (under a microsecond each);
      * "XLA Ops" holds 844 events whose durations add up to 247 759 ns, no
        two overlapping, so busy time is that sum;
      * the Mosaic kernel is the `custom-call` named `sparse_step_fn.1`,
        once a step at 546 ns each: 2 184 ns;
      * no collective (one chip);
      * between the four step programs the device sat idle for 4 259 249,
        3 997 503 and 4 096 207 ns; the loop waited 38, 65 and 61 us of
        those for its input (the block's `wait_s`), and the second gap
        follows global step 22, so none is a log step;
      * inside the step programs 4 x ~79 us less the 246 753 ns of
        operations that ran in them: 68 944 ns idle.
    """
    path = os.path.join(TESTDATA, "tiny_sparse_4steps.xplane.pb")
    with open(os.path.join(TESTDATA, "tiny_sparse_4steps.block.json")) as f:
        block = json.load(f)
    planes = tr.read_planes(path)
    assert [p["name"] for p in planes] == ["/device:TPU:0"]
    assert len(planes[0]["ops"]) == 844 and len(planes[0]["modules"]) == 13
    one = tr.reduce_plane(planes[0])
    assert one["busy_s"] == pytest.approx(247_759e-9)
    assert one["kernel_s"] == pytest.approx(2_184e-9)
    assert one["collective_s"] == 0.0
    assert one["modules"] == 4
    assert one["gaps_s"] == pytest.approx([4_259_249e-9, 3_997_503e-9,
                                           4_096_207e-9])
    assert one["in_step_idle_s"] == pytest.approx(
        (79_122 + 78_642 + 79_094 + 78_839 - 246_753) * 1e-9)
    assert one["kernel_hlo"] and "tpu_custom_call" not in "".join(
        k[:50] for k in one["kernel_hlo"])      # the name leads, target later
    got = tr.reduce_block(os.path.dirname(path), block, log_every=10)
    assert got["steps"] == 4 and got["chips"] == 1
    assert got["busy_s_per_step"] == pytest.approx(247_759e-9 / 4)
    assert got["kernel_s_per_step"] == pytest.approx(546e-9)
    assert got["window_s"] == pytest.approx(block["t1"] - block["t0"])
    waits = block["wait_s"][1:]
    assert got["idle_named"]["data_wait"] == pytest.approx(sum(waits))
    assert got["idle_named"]["log_step"] == 0.0
    assert got["idle_named"]["loop_other"] == pytest.approx(
        (4_259_249 + 3_997_503 + 4_096_207) * 1e-9 - sum(waits))
    assert list(got["kernels"]) == ["sparse_step_fn.1 custom-call"]
    # idle share of the block: the device worked 0.25 ms of 26 ms
    assert 1 - got["busy_s"] / got["window_s"] == pytest.approx(0.9905,
                                                               abs=1e-3)
