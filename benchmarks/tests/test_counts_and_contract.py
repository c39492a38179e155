"""Operation and byte counts against hand counts; the peaks table; the
shape of BENCHMARK.json; the command's refusal to run without a TPU."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmarks import bytes as byte_counts
from benchmarks import flops, harness, trace_reduce

ROOT = harness.ROOT


def config(name):
    with open(os.path.join(harness.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_vgg16_flops_against_a_hand_count():
    # 3x3 convolutions at 32x32 .. 2x2, multiply-adds per example:
    hand = (1024 * 27 * 64 + 1024 * 576 * 64            # 32x32, 64 wide
            + 256 * 576 * 128 + 256 * 1152 * 128        # 16x16, 128
            + 64 * 1152 * 256 + 2 * 64 * 2304 * 256     # 8x8, 256
            + 16 * 2304 * 512 + 2 * 16 * 4608 * 512     # 4x4, 512
            + 3 * 4 * 4608 * 512                        # 2x2, 512
            + 512 * 512 + 512 * 10)                     # the two dense layers
    assert hand == 313_463_808
    cfg = config("vgg16_cifar10")
    assert flops.forward_macs_per_example(cfg) == hand
    assert flops.train_flops_per_example(cfg) == 6 * hand
    assert flops.train_flops_per_step(cfg, 5120) == 6 * hand * 5120


def test_resnet50_flops_against_a_hand_count():
    def block(hw_in, cin, width, stride):
        hw = hw_in // stride
        macs = (hw_in * hw_in * cin * width          # 1x1 at the input size
                + hw * hw * 9 * width * width        # 3x3, carries the stride
                + hw * hw * width * 4 * width)       # 1x1 out
        if stride != 1 or cin != 4 * width:
            macs += hw * hw * cin * 4 * width        # projection shortcut
        return macs, hw, 4 * width

    total = 112 * 112 * 147 * 64                     # 7x7 stem, stride 2
    hw, cin = 56, 64                                 # after the 3x3 max-pool
    for i, (n, width) in enumerate(zip((3, 4, 6, 3), (64, 128, 256, 512))):
        for b in range(n):
            m, hw, cin = block(hw, cin, width, 2 if (i and b == 0) else 1)
            total += m
    total += 2048 * 1000
    assert total == 4_089_184_256                    # the published 4.1 GMACs
    cfg = config("resnet50_imagenet")
    assert flops.forward_macs_per_example(cfg) == total


@pytest.mark.parametrize("name", ["vgg16_cifar10", "resnet50_imagenet"])
def test_the_references_parameters_match_the_configurations(name):
    cfg = config(name)
    ref = harness.load_reference(cfg)
    shapes = ref.param_shapes(cfg)
    count = 0
    for shape in shapes.values():
        n = 1
        for d in shape:
            n *= d
        count += n
    assert count == cfg["arch"]["num_params"]
    # every matrix product of the table is a kernel of the reference
    kernels = [s for p, s in shapes.items() if p.endswith("kernel")]
    assert len(kernels) == len(cfg["matmul_layers"])
    table = sorted(l["k"] * l["n"] for l in cfg["matmul_layers"])
    assert table == sorted(
        int(s[0] * s[1] * s[2] * s[3]) if len(s) == 4 else int(s[0] * s[1])
        for s in kernels)


def test_bytes_against_hand_counts():
    # VGG-16's padded flat gradient on the chip: 15 073 280 float32 values,
    # the residual read and the accumulator written (the gradient operand
    # is not in HBM: the kernel's HLO line as the chip's trace gave it)
    hlo = ("%sparse_step_fn.1 = (f32[117760,128]{1,0:T(8,128)}, "
           "f32[1840,128]{1,0:T(8,128)S(1)}, s32[1840,128]{1,0:T(8,128)S(1)}"
           ", s32[1,1]{1,0:T(1,128)}) custom-call(f32[117760,128]{1,0:T(8,128"
           ")} %bitcast.16, f32[117760,128]{1,0:T(8,128)S(1)} %bitcast.125, "
           "f32[1,1]{1,0:T(1,128)} %constant.223, f32[1,1]{1,0:T(1,128)} "
           "%copy.208), custom_call_target=\"tpu_custom_call\", "
           "operand_layout_constraints={f32[117760,128]{1,0}, "
           "f32[117760,128]{1,0}, f32[1,1]{1,0}, f32[1,1]{1,0}}")
    assert trace_reduce.hbm_passes(hlo) == (2, 15_073_280)
    assert trace_reduce.hbm_passes(hlo.replace("S(1)", "")) == (3, 15_073_280)
    assert trace_reduce.hbm_passes("%fusion.1 = fusion()") == (0, 0)
    assert byte_counts.ef_select_bytes(15_073_280, 2) == 120_586_240
    assert byte_counts.ef_select_bytes(15_073_280, 3) == 180_879_360


def test_an_unknown_device_kind_is_an_error():
    peaks = harness.load_peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.load_peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        harness.load_peaks("cpu")
    with pytest.raises(KeyError):
        harness.load_peaks("_source")


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_keeps_to_the_contract():
    bench = harness.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks"]
    assert 1 <= bench["run_seconds"] <= 51
    cfgs = {c["name"] for c in bench["configs"]}
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank")) for k in c["reduced"])
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        assert os.path.exists(os.path.join(
            harness.HERE, "traffic", w["traffic"] + ".json"))
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(bench["workloads"])
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 4)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m.get("workloads", cells)) <= cells
        # every per-layer metric is a file of its own, found by name
        assert os.path.exists(os.path.join(
            harness.HERE, "layer_metrics", m["name"] + ".py"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_every_cell_loads_with_its_files():
    for w in harness.load_benchmark()["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell["mix"]["nworkers"] == cell["chips"]
        assert "head_grad_rel_err" in cell["config_data"]["limits"]
        for m in cell["per_layer"]:
            assert hasattr(harness.load_layer_metric(cell["metrics_dir"],
                                                     m["name"]), "read")


def test_the_four_chip_cell_is_the_one_chip_cell_on_four_workers():
    """`vgg16_dp4` (PR 26): `dp4_blocks` is `dp1_blocks` with `nworkers` 4
    and nothing else changed; the cell reports every metric that
    `vgg16_dp1` reports, and the exchange's besides."""
    one, four = harness.load_cell("vgg16_dp1"), harness.load_cell("vgg16_dp4")
    differ = {k for k in set(one["mix"]) | set(four["mix"])
              if one["mix"].get(k) != four["mix"].get(k)}
    assert differ == {"name", "what", "nworkers"}
    assert four["mix"]["nworkers"] == four["chips"] == 4
    assert four["config"] == one["config"] and four["arms"] == one["arms"]
    assert [m["name"] for m in four["end_to_end"]] == [
        m["name"] for m in one["end_to_end"]]
    ones = [m["name"] for m in one["per_layer"]]
    fours = [m["name"] for m in four["per_layer"]]
    assert fours == ones + ["exchange_ms"]
    assert {"dense_loop_examples_per_s", "dense_step_device_ms.shared",
            "dense_mfu.shared"} <= set(fours)
    reported = {m["name"] for m in four["end_to_end"]}
    assert all(m["moves"] in reported for m in four["per_layer"])
    exchange = four["per_layer"][-1]
    assert exchange["layer"] == "exchange"
    assert exchange["workloads"] == ["vgg16_dp4"]
    assert exchange["moves"] == "examples_per_s"


def test_the_dense_rate_is_end_to_end_only_where_the_chip_bounds_the_step():
    """In the host-bound cell the dense rate stands among the per-layer
    metrics under another name, and the dense program's readers move the
    rate that the cell does report."""
    vgg, res = harness.load_cell("vgg16_dp1"), harness.load_cell("resnet50_dp1")
    assert "dense_examples_per_s" not in {m["name"] for m in vgg["end_to_end"]}
    assert "dense_examples_per_s" in {m["name"] for m in res["end_to_end"]}
    for cell in (vgg, res):
        reported = {m["name"] for m in cell["end_to_end"]}
        assert len(reported - {"setup_s"}) >= 1
        assert all(m["moves"] in reported for m in cell["per_layer"])
    run = {"totals": {"dense": {"examples_per_s": 123.5}}, "trace": {
        "arms": {"dense": {"busy_s_per_step": 0.002}}}}
    rate = harness.load_layer_metric(vgg["metrics_dir"],
                                     "dense_loop_examples_per_s")
    assert rate.read(run) == 123.5
    twin = harness.load_layer_metric(vgg["metrics_dir"],
                                     "dense_step_device_ms.shared")
    assert twin.read(run) == pytest.approx(2.0)
    assert twin.read({"trace": None}) is None


def test_the_command_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         "vgg16_dp1", "--seed", "3000000019", "--seconds", "1", "--trace",
         "0"], cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert done.stdout.strip() == ""          # no result line, no number
    assert "not run" in done.stderr
