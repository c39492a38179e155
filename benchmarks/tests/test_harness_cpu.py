"""The harness driven on the CPU at a tiny size, through the same functions
a run on the chip goes through, from a throw-away root (conftest.py)."""

import argparse
import copy
import json
import types

import numpy as np
import pytest

from benchmarks import check, harness, run

PEAKS = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}


def drive(tiny_root, capsys, cell_name, seed=5, seconds=1.0):
    """Everything `run.py` does once it has found its chips."""
    cell = harness.load_cell(cell_name, root=tiny_root)
    args = argparse.Namespace(workload=cell_name, seed=seed, seconds=seconds,
                              trace=0)
    out_dir = harness.make_out_dir()
    try:
        rc = run._run(args, cell, PEAKS, harness.CompileLog(), out_dir)
    finally:
        harness.remove_out_dir(out_dir)
    out = capsys.readouterr().out
    return rc, json.loads(out.strip().splitlines()[-1]), out


def test_a_run_end_to_end_on_one_device(tiny_root, capsys):
    rc, result, out = drive(tiny_root, capsys, "tiny_dp1")
    assert rc == 0
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "check"]
    assert result["correct"] is True, out
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"examples_per_s",
                                      "dense_examples_per_s", "step_ms_p95",
                                      "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"     # never a device number
    assert "check loss_gap:" in out and "check lost:" in out
    # each number compared beside its limit, last in the result line
    limits = harness.load_cell("tiny_dp1", root=tiny_root)[
        "config_data"]["limits"]
    assert {k: v["limit"] for k, v in result["check"].items()} == limits
    assert result["check"]["lost"]["value"] == 0
    # what the run holds: both arms' state, the window's and the check's
    memory = result["device"]["memory"]
    assert set(memory["state_bytes"]) == {"dense", "sparse"}
    assert set(memory["check"]) >= {"arrays_peak_bytes", "peak_bytes"}
    assert "device memory, fullest chip" in out
    assert "device memory, the check:" in out
    assert memory["first_step_bytes"]["sparse"]["with_program"] >= \
        memory["first_step_bytes"]["sparse"]["in_use"] >= 0
    # where the seconds went, by part: two lines, the same in the result,
    # and the parts add up to the totals they stand beside
    seconds = result["device"]["seconds"]
    assert seconds["set_up"]["total_s"] == \
        result["metrics"]["setup_s"]["value"]
    wanted = {"set_up": ["to the run", "weights from seed", "dense trainer",
                         "sparse trainer", "dense first steps",
                         "sparse first steps", "dense warm-up",
                         "sparse warm-up"],
              "check": ["the program's readings",
                        "rows and masks to the reference's order",
                        "reference: gradient program compiled",
                        "reference: parameters to the device",
                        "reference: gradient calls",
                        "reference: gradients to the host",
                        "reference: step arithmetic on the host",
                        "gradients and parameters to the program's order",
                        "compare", "lost_entries", "judge"]}
    for title, key in (("set-up by part:", "set_up"),
                       ("check by part:", "check")):
        line = [l for l in out.splitlines() if l.startswith(title)]
        assert len(line) == 1, title
        rec = seconds[key]
        assert set(wanted[key]) <= set(rec["parts"]), rec["parts"]
        assert all(name in line[0] for name in rec["parts"])
        assert f"{title} {rec['total_s']:.2f}s" in line[0]
        named = sum(rec["parts"].values())
        assert rec["unnamed_s"] == pytest.approx(rec["total_s"] - named)
        # within 2 %, and at this size the interpreter's few hundredths
        # of a second between the parts
        assert abs(rec["unnamed_s"]) <= 0.02 * rec["total_s"] + 0.05, line[0]
    # compilation inside a part stands apart: the step programs' is in the
    # first steps, the reference's in its own part
    assert "(compile " in out.split("set-up by part:")[1].splitlines()[0]
    assert "sparse first steps by part:" in out


def test_a_run_end_to_end_on_four_devices(tiny_root, capsys):
    rc, result, out = drive(tiny_root, capsys, "tiny_dp4")
    assert rc == 0 and result["correct"] is True, out
    assert "check residual_devices: 4" in out


# ------------------------------------------------------- arms by the mix

@pytest.mark.parametrize("cell_name,devices", [("tiny_solo1", 1),
                                               ("tiny_solo4", 4)])
def test_a_mix_with_one_arm_runs_one_trainer(tiny_root, capsys, cell_name,
                                             devices):
    """A mix whose round is ["sparse"]: no dense trainer is built, driven,
    totalled or checked, and the metric that needs it is not reported."""
    rc, result, out = drive(tiny_root, capsys, cell_name)
    assert rc == 0 and result["correct"] is True, out
    assert set(result["metrics"]) == {"examples_per_s", "step_ms_p95",
                                      "setup_s"}
    assert set(result["device"]["memory"]["state_bytes"]) == {"sparse"}
    assert "dense trainer built" not in out and "sparse:dense" not in out
    assert "sparse trainer built" in out
    assert f"check residual_devices: {devices}" in out
    assert not [l for l in out.splitlines() if l.startswith("block")
                and " dense " in l]
    assert "dense_delta_total_norm_gap" not in out


def test_a_cell_that_lists_a_metric_of_the_absent_arm_is_refused(
        tiny_root, tmp_path, monkeypatch):
    """Before any trainer is built: `load_cell` is the first thing a run
    does with its cell."""
    import shutil
    root = str(tmp_path / "root")
    shutil.copytree(tiny_root, root)
    with open(f"{root}/BENCHMARK.json") as f:
        bench = json.load(f)
    dense = [m for m in bench["end_to_end"]
             if m["name"] == "dense_examples_per_s"][0]
    dense["workloads"].append("tiny_solo1")
    with open(f"{root}/BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    monkeypatch.setattr(harness, "build_arms", None)
    with pytest.raises(SystemExit, match="dense_examples_per_s.*does not run"):
        harness.load_cell("tiny_solo1", root=root)
    assert harness.load_cell("tiny_dp1", root=root)["arms"] == ["dense",
                                                                "sparse"]
    for rnd in (["dense"], ["sparse", "control"], []):
        with pytest.raises(SystemExit, match="names the sparse trainer"):
            harness.arm_names({"name": "bad", "round": rnd})


@pytest.mark.parametrize("cell_name,workers", [("tiny_dp1", 1),
                                               ("tiny_dp4", 4)])
def test_a_part_of_the_batch_left_out_is_not_correct(tiny_root, capsys,
                                                     cell_name, workers):
    """The timed path broken underneath: the trainers are fed batches in
    which one worker's rows repeat another's (with one worker: the second
    half repeats the first), the reference sees the rows as drawn."""
    from benchmarks import calibrate
    with calibrate.worker_rows_left_out(workers):
        rc, result, out = drive(tiny_root, capsys, cell_name)
    assert rc == 0 and result["correct"] is False
    assert "FAILED" in out


def test_a_step_that_leaves_the_parameters_alone_is_not_correct(
        tiny_root, capsys, monkeypatch):
    sound_argv = harness.trainer_argv

    def argv_with_no_step(config, *a, **kw):
        config = copy.deepcopy(config)
        config["trainer"]["lr"] = 0.0
        return sound_argv(config, *a, **kw)

    monkeypatch.setattr(harness, "trainer_argv", argv_with_no_step)
    rc, result, out = drive(tiny_root, capsys, "tiny_dp1")
    assert result["correct"] is False
    assert "check delta_norm_gap: 1 " in out and "FAILED" in out


def test_an_arm_that_sits_out_produces_nothing(tiny_root):
    """The idle trainer's input stream stands still behind its gate. (Here
    the producer is the faster side: the program's queue of two is full and
    a third batch in the producer's hand when the turn ends.)"""
    import time
    cell = harness.load_cell("tiny_dp1", root=tiny_root)
    out_dir = harness.make_out_dir()
    try:
        arms, _ = harness.build_arms(cell, 3, out_dir, False)
        arm = arms["sparse"]
        arm.train(4)
        time.sleep(0.3)
        made = sum(s.pulls for s in arm.streams)
        assert 4 <= made <= 4 + 3
        arms["dense"].train(4)
        time.sleep(0.3)
        assert sum(s.pulls for s in arm.streams) == made
        arm.train(2)
        assert arm.feed.mark() == 6
        harness.close_arms(arms)
    finally:
        harness.remove_out_dir(out_dir)


def test_batches_that_do_not_come_through_the_gate_are_refused(tiny_root):
    """Should the program stop making its batches in `Trainer._stream()`,
    the gate would hold nothing, and the run says so instead of timing it."""
    import itertools
    cell = harness.load_cell("tiny_dp1", root=tiny_root)
    out_dir = harness.make_out_dir()
    try:
        arms, _ = harness.build_arms(cell, 3, out_dir, False)
        arm = arms["dense"]
        arm.train(1)
        arm.feed = harness.TimedFeed(itertools.repeat(arm.feed.kept[0]))
        arm.streams.clear()
        with pytest.raises(RuntimeError, match="Trainer._stream"):
            arm.train(1)
        harness.close_arms(arms)
    finally:
        harness.remove_out_dir(out_dir)


# ------------------------------------------------ doctored states, control

@pytest.fixture(scope="module")
def sound(tiny_root):
    """One sound set of readings of the tiny cell, taken once."""
    cell = harness.load_cell("tiny_dp1", root=tiny_root)
    out_dir = harness.make_out_dir()
    try:
        arms, weights = harness.build_arms(cell, 9, out_dir, False)
        for arm in arms.values():
            harness.first_steps(arm, cell["config_data"])
        harness.warm_up(arms["sparse"], cell["mix"])
        firsts = {n: types.SimpleNamespace(name=n, first=a.first)
                  for n, a in arms.items()}
        harness.close_arms(arms)
    finally:
        harness.remove_out_dir(out_dir)
    return cell, firsts, weights


def verdict(cell, firsts, weights):
    # `run_check` consumes the readings it is given
    ok, numbers, lines, _ = check.run_check(
        cell, 9, copy.deepcopy(firsts), weights,
        {"compiles_in_window": 0, "failed_steps": 0})
    return ok, numbers, lines


def bf16(x):
    import ml_dtypes
    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


def wd_p0(cell, firsts, weights):
    like = firsts["sparse"].first["params"]
    p0 = np.concatenate([weights[p].reshape(-1) for p in like])
    return np.float32(cell["config_data"]["trainer"]["weight_decay"]) * p0


def test_the_tree_as_it_stands_is_correct(sound):
    ok, numbers, lines = verdict(*sound)
    assert ok, lines
    assert numbers["sent_mantissa"] > 5e-4 and numbers["lost"] == 0


DOCTORS = {}


def doctor(fn):
    DOCTORS[fn.__name__] = fn
    return fn


@doctor
def residual_rounded_to_bf16(cell, f, weights):
    f["residual1"] = bf16(f["residual1"])
    return "residual_mantissa"


@doctor
def wire_values_rounded_to_bf16(cell, f, weights, firsts=None):
    quiet = wd_p0(cell, firsts, weights)
    n = quiet.size
    f["momentum1"][:n] = quiet + bf16(f["momentum1"][:n] - quiet)
    return "sent_mantissa"


@doctor
def accumulator_rounded_to_bf16(cell, f, weights):
    f["momentum1"] = bf16(f["momentum1"])
    return "momentum_mantissa"


@doctor
def part_of_the_update_dropped(cell, f, weights, firsts=None):
    quiet = wd_p0(cell, firsts, weights)
    n = quiet.size
    sent = np.flatnonzero(f["momentum1"][:n] != quiet)
    f["momentum1"][sent[::2]] = quiet[sent[::2]]
    return "lost"


@doctor
def part_of_the_update_sent_and_kept(cell, f, weights, firsts=None):
    quiet = wd_p0(cell, firsts, weights)
    n = quiet.size
    sent = np.flatnonzero(f["momentum1"][:n] != quiet)
    f["residual1"][0, sent[::2]] = 1e-3
    return "double_counted"


@doctor
def update_misplaced_by_one(cell, f, weights):
    f["momentum1"] = np.roll(f["momentum1"], 1)
    return "grad_rel_err"


@doctor
def selection_out_of_its_band(cell, f, weights):
    f["warm_selected"] = 400.0 * f["k"]
    return "selected_over_k"


@pytest.mark.parametrize("name", sorted(DOCTORS))
def test_a_doctored_state_is_not_correct(sound, name):
    cell, firsts, weights = sound
    doctored = copy.deepcopy(firsts)
    fn = DOCTORS[name]
    kw = ({"firsts": firsts} if "firsts" in fn.__code__.co_varnames else {})
    number = fn(cell, doctored["sparse"].first, weights, **kw)
    ok, numbers, lines = verdict(cell, doctored, weights)
    assert not ok
    failed = [l for l in lines if "FAILED" in l]
    assert any(f"check {number}:" in l for l in failed), lines


def test_the_control_in_float8_is_not_correct(sound):
    """The reference in the program's place, one precision below the
    configuration's bfloat16, fails a limit (loss_gap and grad_rel_err at
    this size); in float32 it passes every one of them."""
    cell, firsts, weights = sound
    config, mix = cell["config_data"], cell["mix"]
    batches = {n: a.first["batches"] for n, a in firsts.items()}
    masks = firsts["sparse"].first["masks"]
    ref = check.reference_readings(config, mix, 9, batches, masks, weights)
    limits = {k: v for k, v in config["limits"].items()
              if k in ("loss_gap", "grad_rel_err", "grad_norm_gap",
                       "delta_norm_gap")}
    low = check.reference_readings(config, mix, 9, batches, masks, weights,
                                   precision="float8")
    ok, lines = check.judge(check.compare(low, ref), limits)
    assert not ok, lines
    ok, lines = check.judge(check.compare(ref, ref), limits)
    assert ok, lines


def test_calibrate_reads_what_a_run_compares(tiny_root, sound):
    """`calibrate.py` goes one arm after the other, as `run_check` does,
    and reads the same numbers of the same seed; its control is the float8
    reference in the program's place."""
    from benchmarks import calibrate
    cell, firsts, weights = sound
    _, numbers, _ = verdict(cell, firsts, weights)
    row = calibrate.readings(cell, 9, control=True)
    for key, v in row["sound"].items():
        assert numbers[key] == v, key
    assert {"loss_gap", "grad_rel_err", "grad_norm_gap",
            "delta_norm_gap"} <= set(row["control"])
    assert row["control"]["loss_gap"] > 3 * row["sound"]["loss_gap"]
    # each set of readings held to the limits, as a run holds its own
    assert row["verdict"] == {"correct": True, "failed": []}
    assert row["control_verdict"]["correct"] is False
    assert row["control_verdict"]["failed"]
    broken = calibrate.readings(cell, 9, control=False, fault=True)
    assert broken["verdict"]["correct"] is False and broken["fault"]
    assert set(row["leaves"]["sound"]) == {
        "dense.first_grad", "dense.delta", "sparse.first_grad",
        "sparse.delta"}
    assert set(row["leaves"]["control"]) == set(row["leaves"]["sound"])
    # what the seed cost, by part, on each side
    assert set(row["seconds"]) == {"program", "reference", "control"}
    for side, total in (("program", row["program_s"]),
                        ("reference", row["reference_s"]),
                        ("control", row["control_s"])):
        rec = row["seconds"][side]
        assert rec["total_s"] == total and rec["parts"]
        assert abs(rec["unnamed_s"]) <= 0.05 * total + 0.05, (side, rec)
    assert "reference: gradient calls" in row["seconds"]["control"]["parts"]
    assert "sparse first steps" in row["seconds"]["program"]["parts"]


# ------------------------------------------------------ driven by data

def test_new_cells_configs_mixes_and_metrics_are_files_and_entries(tiny_root):
    """The throw-away root adds one of each without a file of
    `benchmarks/` being edited: the harness finds them by name."""
    cell = harness.load_cell("tiny_dp4", root=tiny_root)
    assert cell["config_data"]["name"] == "tiny_vgg"
    assert cell["mix"]["nworkers"] == 4
    names = [m["name"] for m in cell["per_layer"]]
    assert names == ["sparse_steps", "data_wait_ms", "ef_select_ms"]
    run_stub = {"totals": {"sparse": {"steps": 7, "wait_s": [0.001, 0.003]}},
                "trace": None}
    new = harness.load_layer_metric(cell["metrics_dir"], "sparse_steps")
    assert new.read(run_stub) == 7.0
    shipped = harness.load_layer_metric(cell["metrics_dir"], "data_wait_ms")
    assert shipped.read(run_stub) == pytest.approx(2.0)
    # a reader that finds nothing to read returns nothing
    absent = harness.load_layer_metric(cell["metrics_dir"], "ef_select_ms")
    assert absent.read(run_stub) is None


def test_the_windows_layout_is_fixed_by_the_seconds():
    mix = {"round": ["dense", "sparse", "sparse"], "block_seconds": 5.0}
    plan = harness.plan_blocks(mix, 30, trace=True)
    assert [b["arm"][0] for b in plan] == list("dsssSd".lower())
    # each arm's second block runs under the profiler
    assert [b["traced"] for b in plan] == [False, False, True, False, False,
                                           True]
    assert not any(b["traced"] for b in harness.plan_blocks(mix, 30, False))
    assert len(harness.plan_blocks(mix, 2, False)) == 3      # one round
    # at the benchmark's own run_seconds: three dense blocks and seven sparse
    long = harness.plan_blocks(mix, 51, trace=True)
    assert "".join(b["arm"][0] for b in long) == "dssssddsss"
    assert [i for i, b in enumerate(long) if b["traced"]] == [2, 5]


def test_block_order_turns_round():
    mix = {"round": ["dense", "sparse", "sparse"]}
    it = harness.block_order(mix)
    got = [next(it) for _ in range(9)]
    assert got == ["dense", "sparse", "sparse", "sparse", "sparse", "dense",
                   "dense", "sparse", "sparse"]
    assert got.count("sparse") == 6
