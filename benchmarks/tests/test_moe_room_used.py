"""The reader of `moe_room_used` (PR 39) on `train` records: the mean over
the counted log steps, and nothing where the program logs no such counter
(a parent of PR 39 on the same benchmark files)."""

import pytest

from benchmarks import harness


def the_reader():
    cell = harness.load_cell("mellum2_moe_dp1")
    return harness.load_layer_metric(cell["metrics_dir"], "moe_room_used")


def test_the_room_used_is_the_counted_log_steps_mean():
    reader = the_reader()
    run = {"train_records": [{"moe_room_used": 0.5, "moe_held_assignments": 9},
                             {"moe_room_used": 0.53}, {"step": 7}]}
    assert reader.read(run) == pytest.approx(0.515)


def test_a_program_without_the_counter_reads_nothing():
    reader = the_reader()
    assert reader.read({"train_records": [
        {"moe_held_assignments": 66000.0}]}) is None
    assert reader.read({"train_records": []}) is None
    assert reader.read({"blocks": {"sparse": []}}) is None


def test_the_benchmark_lists_it_for_the_three_transformer_cells():
    entry, = [m for m in harness.load_benchmark()["per_layer"]
              if m["name"] == "moe_room_used"]
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "model" and entry["moves"] == "examples_per_s"
    assert entry["workloads"] == ["mellum2_moe_dp1", "joyai_mla_dp1",
                                  "lfm2_conv_dp1"]
