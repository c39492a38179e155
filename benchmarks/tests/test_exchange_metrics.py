"""`exchange_ms`, and the collectives that it reads, on two small traces
recorded on FOUR TPU v5 lite chips by `record_exchange_trace.py` (PR 26), kept under
`benchmarks/testdata/`: two steps of the tests' tiny four-worker cell, one
block of the sparse trainer and one of the dense baseline."""

import json
import os
import shutil

import pytest

from benchmarks import harness, span_reduce as sr, trace_reduce

TESTDATA = os.path.join(harness.HERE, "testdata")
METRICS = os.path.join(harness.HERE, "layer_metrics")

# By hand, from a listing of every device plane's "XLA Ops" (the raw
# decoder of `span_reduce`, which `test_span_reduce.py` holds against
# `ProfileData`): the operations whose opcode is a collective, picoseconds
# summed over the block's two steps, chip by chip. Sparse: `all-reduce.14`,
# `psum.73` and `all-reduce.15` in each step; dense: `all-reduce.12` and
# `psum.72`.
BY_HAND_PS = {"sparse": [24_871_718, 23_547_188, 23_372_734, 20_670_860],
              "dense": [29_493_750, 26_981_484, 26_717_266, 26_085_938]}


@pytest.fixture(scope="module", params=["sparse", "dense"])
def recorded(request, tmp_path_factory):
    arm = request.param
    stem = os.path.join(TESTDATA, f"tiny_dp4_{arm}_2steps")
    tdir = str(tmp_path_factory.mktemp(f"dp4_{arm}"))
    # kept as `.xspace.pb`: `test_trace_reduce.py` reduces every
    # `*.xplane.pb` under testdata/ as one trace
    shutil.copy(stem + ".xspace.pb", os.path.join(tdir, arm + ".xplane.pb"))
    with open(stem + ".block.json") as f:
        return arm, tdir, json.load(f)


def test_the_collectives_of_four_chips_by_hand(recorded):
    arm, tdir, block = recorded
    assert block["steps"] == 2 and block["arm"] == arm
    path = trace_reduce.find_xplanes(tdir)[0]
    planes = [p for p in sr.read_xspace(path)
              if p["name"].startswith(trace_reduce.DEVICE_PLANE_PREFIX)]
    assert [p["name"] for p in planes] == [f"/device:TPU:{i}"
                                           for i in range(4)]
    got = [sum(dur for name, _, dur, _ in p["lines"][sr.OPS_LINE]
               if trace_reduce.is_collective(name)) for p in planes]
    assert got == BY_HAND_PS[arm]
    # why the readers go by opcode: the program's scope `exchange` is on
    # no operation of the compiled program. XLA's combiner merges the
    # all-reduces and keeps one name: the guard's, the metrics', or none
    scopes = {sr.scope_of(tf_op) for p in planes
              for name, _, _, tf_op in p["lines"][sr.OPS_LINE]
              if trace_reduce.is_collective(name)}
    assert scopes == ({"guard", "step_metrics", None} if arm == "sparse"
                      else {"guard", "step_metrics"})
    dev = sr.reduce_device(tdir, block["steps"])
    assert dev["chips"] == 4 and "exchange" not in dev["scope_s_per_step"]


def test_the_exchange_reader_on_the_recorded_blocks(recorded):
    """Through the run's own reduction: the chips' mean of the collective
    operations' device time per step, which a traced run prints for either
    arm and `exchange_ms` reads, in milliseconds, for the sparse one."""
    arm, tdir, block = recorded
    run = {"blocks": {arm: [block]}, "log_every": 10}
    run["trace"] = trace_reduce.reduce_run({arm: [tdir]}, run)
    assert run["trace"]["arms"][arm]["chips"] == 4
    got = 1e3 * run["trace"]["arms"][arm]["collective_s_per_step"]
    # `ProfileData` hands durations out in whole nanoseconds
    assert got == pytest.approx(1e3 * sum(BY_HAND_PS[arm]) / 1e12 / 4 / 2,
                                rel=1e-3)
    assert got == pytest.approx(
        {"sparse": 0.0115565, "dense": 0.013659125}[arm], rel=1e-9)
    reader = harness.load_layer_metric(METRICS, "exchange_ms")
    # the dense block holds nothing of the sparse arm: nothing to read
    assert reader.read(run) == (got if arm == "sparse" else None)


def test_on_one_chip_there_is_no_exchange_to_read():
    """The one-chip recording: the collectives over a one-device axis are
    gone from the compiled program, and the reader returns nothing, never
    0."""
    with open(os.path.join(TESTDATA, "tiny_sparse_4steps.block.json")) as f:
        block = json.load(f)
    run = {"blocks": {"sparse": [block]}, "log_every": 10}
    run["trace"] = trace_reduce.reduce_run({"sparse": [TESTDATA]}, run)
    assert run["trace"]["arms"]["sparse"]["chips"] == 1
    reader = harness.load_layer_metric(METRICS, "exchange_ms")
    assert reader.read(run) is None
    assert reader.read({"trace": None}) is None


def test_on_four_chips_a_program_without_collectives_reads_zero():
    """If the collectives vanish from a four-chip program, the exchange is
    gone: that reads 0 ms, not nothing."""
    run = {"trace": {"arms": {"sparse": {"chips": 4,
                                         "collective_s_per_step": 0.0}}}}
    assert harness.load_layer_metric(METRICS, "exchange_ms").read(run) == 0.0
