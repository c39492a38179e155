"""The configuration `mellum2_12b_a2p5b` (PR 31) through the harness at a
tiny size on the CPU, from a throw-away root that this file writes (files
and entries only; the reference and the readers are the real ones, found by
name), and its new readers on doctored runs and on the recorded trace of a
program that names none of the model's scopes."""

import argparse
import copy
import json
import os
import shutil

import pytest

from benchmarks import attn_ops, flops, harness, model_scopes, moe_ops, run
from benchmarks import trace_reduce
from test_harness_cpu import drive

CELL = "mellum2_moe_dp1"
NEW_METRICS = ["attn_window_ms", "attn_full_ms", "moe_experts_ms",
               "moe_router_ms", "lm_head_ms", "moe_load_max_over_mean",
               "sparse_mfu", "splash_fwd_roofline", "splash_dq_roofline",
               "splash_dkv_roofline", "ragged_dot_ms", "ragged_dot_roofline"]
SCOPE_METRICS = NEW_METRICS[:5]
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def real_config() -> dict:
    return harness.load_cell(CELL)["config_data"]


def tiny_config() -> dict:
    """The real file with every size made tiny: what the reference reads
    (the published config's own keys) and what the trainer is given."""
    cfg = copy.deepcopy(real_config())
    positions, vocab, steps = 32, 50, 6
    cfg.update(
        name="tiny_mellum2", hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
        num_experts=4, num_experts_per_tok=2, sliding_window=8,
        vocab_size=vocab, published={"num_experts": 8},
        share={"expert_share": 1, "expert_shares": 2},
        examples_per_worker=steps,
        dataset_kwargs={"vocab_size": vocab, "bptt": positions},
        dataset_kwargs_per_worker={
            "synthetic_tokens_n": 2 * (positions * steps + 1)})
    cfg["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"] = 16
    cfg["trainer"].update(
        compute_dtype="float32", wire="off", density=0.01,
        model_kwargs={
            "hidden_size": 64, "num_layers": 4, "num_heads": 4,
            "num_kv_heads": 2, "head_dim": 16, "sliding_window": 8,
            "num_experts": 8, "experts_per_token": 2, "expert_width": 32,
            "expert_share": 1, "expert_shares": 2, "yarn_original_max": 16,
            "layer_types": cfg["layer_types"][:4], "seq_len": positions})
    cfg["states"].update(compute_dtype="float32", kernel_mode="interpret")
    cfg["matmul_layers"] = [{"name": "lm_head", "positions": positions,
                             "k": 64, "n": vocab}]
    cfg["arch"]["expert_product_macs_per_assignment"] = 3 * 64 * 32
    # float32 throughout: the sound runs read 1e-6 at most, the float8
    # control 1e-2 at least (tests/test_mellum2.py has both at this size)
    cfg["limits"].update(
        loss_gap_first=1e-4, loss_gap=1e-4, head_grad_rel_err=1e-3,
        grad_rel_err=1e-3, grad_norm_gap=1e-3, delta_norm_gap=1e-3,
        selected_over_k=[0.2, 200.0])
    return cfg


@pytest.fixture(scope="module")
def mellum_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny_mellum2"))
    bdir = os.path.join(root, "benchmarks")
    for sub in ("configs", "traffic"):
        os.makedirs(os.path.join(bdir, sub))
    with open(os.path.join(bdir, "configs", "tiny_mellum2.json"), "w") as f:
        json.dump(tiny_config(), f)
    mix = dict(harness.load_cell(CELL)["mix"], block_seconds=0.2)
    with open(os.path.join(bdir, "traffic", "dp1_sparse_blocks.json"),
              "w") as f:
        json.dump(mix, f)
    real = harness.load_benchmark()
    bench = {
        "command": real["command"], "paths": ["benchmarks"], "run_seconds": 1,
        "configs": [{"name": "tiny_mellum2", "source": "throw-away",
                     "file": "benchmarks/configs/tiny_mellum2.json",
                     "reduced": [], "why": "test"}],
        "workloads": [{"name": "tiny_moe", "config": "tiny_mellum2",
                       "traffic": "dp1_sparse_blocks", "chips": 1,
                       "why": "test"}],
        "end_to_end": [m for m in real["end_to_end"]
                       if m["name"] != "dense_examples_per_s"],
        "per_layer": [dict(m, workloads=["tiny_moe"])
                      for m in real["per_layer"]
                      if CELL in m.get("workloads", ())]}
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_the_configuration_runs_end_to_end_on_the_cpu(mellum_root, capsys):
    rc, result, out = drive(mellum_root, capsys, "tiny_moe")
    assert rc == 0 and result["correct"] is True, out
    assert set(result["metrics"]) == {"examples_per_s", "step_ms_p95",
                                      "setup_s"}
    assert "sparse trainer built" in out and "dense trainer" not in out
    assert result["check"]["head_grad_rel_err"]["value"] < 1e-4
    assert result["check"]["lost"]["value"] == 0
    assert result["failed"] == 0 and result["attempted"] > 0


def test_a_traced_run_reads_the_counters_and_nothing_of_the_device(
        mellum_root, capsys, monkeypatch):
    """`--trace 1` on the CPU: no device plane, so the old reduction is
    stood in for with a busy time and the scope and kernel readers find
    nothing; the counters come from the trainer's own `train` records."""
    monkeypatch.setattr(
        trace_reduce, "reduce_run", lambda traced, run: {
            "arms": {"sparse": {
                "steps": 2, "chips": 1, "busy_s": 1.0, "window_s": 2.0,
                "busy_s_per_step": 0.5, "kernels": {}, "kernel_hlo": [],
                "collective_s_per_step": 0.0, "idle_named": {}}},
            "busy_s": 1.0, "window_s": 2.0,
            "breakdown": {"device_ops": [], "idle_gaps": []}})
    cell = harness.load_cell("tiny_moe", root=mellum_root)
    args = argparse.Namespace(workload="tiny_moe", seed=9, seconds=1.0,
                              trace=1)
    out_dir = harness.make_out_dir()
    try:
        rc = run._run(args, cell, V5E, harness.CompileLog(), out_dir)
    finally:
        harness.remove_out_dir(out_dir)
    out = capsys.readouterr().out
    result = json.loads(out.strip().splitlines()[-1])
    assert rc == 0 and result["correct"] is True, out
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["moe_load_max_over_mean"] >= 1.0
    assert 0 < m["sparse_mfu"] < 1e-3           # a CPU's, and tiny
    assert not set(SCOPE_METRICS) & set(m)
    assert not {n for n in m if n.endswith("_roofline")}
    assert {"fwd_bwd_ms", "ef_select_ms"} & set(m) == set()
    assert "run_ahead_share" in m and "compile_s" in m


def test_the_real_benchmark_has_the_cell_and_its_readers():
    bench = harness.load_benchmark()
    cell = harness.load_cell(CELL)
    assert cell["arms"] == ["sparse"] and cell["chips"] == 1
    assert [m["name"] for m in cell["end_to_end"]] == [
        "examples_per_s", "step_ms_p95", "setup_s"]
    names = [m["name"] for m in cell["per_layer"]]
    assert set(NEW_METRICS) <= set(names)
    for m in bench["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "examples_per_s"
            assert os.path.exists(os.path.join(
                harness.HERE, "layer_metrics", m["name"] + ".py"))
    # the kernel readers of the other cells add up every Mosaic call of a
    # step, and this model brings calls of its own
    assert not {"ef_select_ms", "ef_select_roofline", "dense_mfu",
                "exchange_ms"} & set(names)
    assert sum(1 for w in bench["workloads"] if w["chips"] == 4) == 1
    config = cell["config_data"]
    assert config["arch"]["num_params"] == 340349184
    assert [config[k] for k in ("hidden_size", "num_attention_heads",
                                "num_key_value_heads", "head_dim",
                                "moe_intermediate_size",
                                "num_experts_per_tok", "sliding_window")] == [
        2304, 32, 4, 128, 896, 8, 1024]
    entry = [c for c in bench["configs"] if c["name"] == cell["config"]][0]
    assert entry["reduced"] == list(config["reduced"]) == [
        "num_hidden_layers", "num_experts", "vocab_size", "nworkers"]


def test_pairs_under_each_mask_against_a_count():
    for s, w in ((32, None), (32, 8), (32, 1), (32, 32), (32, 40), (5, 2)):
        want = sum(1 for i in range(s) for j in range(s)
                   if 0 <= i - j and (w is None or i - j < w))
        assert attn_ops.pairs(s, w) == want
    config = real_config()
    assert attn_ops.layer_pairs(config) == [7864832] * 3 + [33558528]
    listed = [l["positions"] for l in config["matmul_layers"]
              if l["name"].endswith(".scores")]
    assert listed == attn_ops.layer_pairs(config)


def doctored_run(**over):
    config = real_config()
    r = {"config": config, "cell": {"chips": 1}, "peaks": V5E,
         "mix": {"nworkers": 1}, "global_batch": {"sparse": 2},
         "blocks": {"sparse": []}, "trace": None}
    r.update(over)
    return r


def test_sparse_mfu_cannot_pass_100(monkeypatch):
    """At the least time the step's operations could take it reads 100, at
    any longer busy time less; the experts' products follow the counter."""
    reader = harness.load_layer_metric(
        os.path.join(harness.HERE, "layer_metrics"), "sparse_mfu")
    config = real_config()
    held = 4 * 16384.0
    monkeypatch.setattr(model_scopes, "counter",
                        lambda run, name: held)
    need = (flops.train_flops_per_step(config, 2)
            + 6 * 3 * 2304 * 896 * held)
    # 394 MFLOP a token forward, as the issue reckons
    assert need / 3 / 16384 == pytest.approx(392.5e6, rel=0.01)
    least = need / 197e12
    for busy, want in ((least, 100.0), (2 * least, 50.0), (0.45, None)):
        r = doctored_run(trace={"arms": {"sparse": {
            "busy_s_per_step": busy}}})
        got = reader.read(r)
        assert got == pytest.approx(want or 100.0 * least / busy)
        assert got <= 100.0 + 1e-9
    # no counter (a parent, another model), or an untraced run: nothing
    assert reader.read(doctored_run()) is None
    monkeypatch.setattr(model_scopes, "counter", lambda run, name: None)
    assert reader.read(doctored_run(trace={"arms": {"sparse": {
        "busy_s_per_step": 0.4}}})) is None


def test_a_kernels_roofline_share_from_its_calls(monkeypatch):
    config = real_config()
    fwd = attn_ops.flops_per_pass(config, "splash_mqa_fwd_residuals", 2)
    assert fwd == 2 * 2 * 128 * 32 * (3 * 7864832 + 33558528) * 2
    assert attn_ops.flops_per_pass(
        config, "splash_mqa_dkv_no_residuals", 2) == 2 * fwd
    least = fwd / 197e12
    assert attn_ops.bytes_per_pass(
        config, "splash_mqa_fwd_residuals", 2) / 819e9 < least
    # eight calls a step over four layers: forward and recomputed forward
    monkeypatch.setattr(model_scopes, "kernel", lambda run, name: {
        "s_per_step": 4 * least, "calls_per_step": 8.0})
    assert attn_ops.roofline_share(
        doctored_run(), "splash_mqa_fwd_residuals") == pytest.approx(50.0)
    monkeypatch.setattr(model_scopes, "kernel", lambda run, name: None)
    assert attn_ops.roofline_share(
        doctored_run(), "splash_mqa_fwd_residuals") is None


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(step)/fwd_bwd/Mellum2/layers_0/attn/attn_window/dot", "attn_window"),
    ("jit(step)/fwd_bwd/transpose(jvp(Mellum2))/layers_3/attn/"
     "attn_full/custom_vjp_call", "attn_full"),
    ("jit(step)/fwd_bwd/checkpoint/rematted_computation/Mellum2/layers_1/"
     "moe/moe_experts/ragged_dot", "moe_experts"),
    ("jit(step)/fwd_bwd/Mellum2/layers_1/moe/moe_router/top_k", "moe_router"),
    ("jit(step)/fwd_bwd/transpose(jvp(Mellum2))/lm_head/dot", "lm_head"),
    ("jit(step)/fwd_bwd/Mellum2/layers_1/moe/mul", None),
    ("jit(step)/update/mul", None)])
def test_the_innermost_model_scope_of_an_op_name(tf_op, scope):
    """The scopes are the configuration's own list; without one, nothing."""
    scopes = real_config()["model_scopes"]
    assert model_scopes.scope_of(tf_op, scopes) == scope
    assert model_scopes.scope_of(tf_op, ()) is None


def test_a_kernel_without_a_scope_is_counted_where_the_configuration_says(
        tmp_path):
    """On the recording with the program's scopes (`tiny_spans_4steps`): the
    EF+select kernel is under none of the scopes asked for, and a
    configuration that gives it to `update` finds its time there, once."""
    shutil.copy(os.path.join(harness.HERE, "testdata",
                             "tiny_spans_4steps.xspace.pb"),
                str(tmp_path / "x.xplane.pb"))
    plain = model_scopes.reduce_device(str(tmp_path), 4, ("update",))
    given = model_scopes.reduce_device(str(tmp_path), 4, ("update",),
                                       {"ef_select": "update", "none": "x"})
    kernel = plain["kernels"]["ef_select"]["s_per_step"]
    assert kernel > 0 and given["kernels"] == plain["kernels"]
    assert set(given["scope_s_per_step"]) == {"update"}
    assert given["scope_s_per_step"]["update"] == pytest.approx(
        plain["scope_s_per_step"]["update"] + kernel)
    # a kernel that carries a scope of the list stays where its name puts it
    named = model_scopes.reduce_device(str(tmp_path), 4, ("ef_select",),
                                       {"ef_select": "update"})
    assert set(named["scope_s_per_step"]) == {"ef_select"}
    assert model_scopes.reduce_device(str(tmp_path), 4) == {
        "scope_s_per_step": {}, "kernels": plain["kernels"]}


def test_the_grouped_products_roofline_share_from_the_rows_held(monkeypatch):
    """48 calls a step over 4 layers are 4 passes of 3 products; the
    operations follow the counter; bound by operations."""
    config = real_config()
    held = 4 * 16384.0
    assert moe_ops.flops_per_pass(config, held) == 2 * 3 * 2304 * 896 * held
    least = moe_ops.flops_per_pass(config, held) / 197e12
    assert moe_ops.bytes_per_pass(config, held) / 819e9 < least
    assert config["kernels_without_scope"][moe_ops.KERNEL] == "moe_experts"
    ms = harness.load_layer_metric(
        os.path.join(harness.HERE, "layer_metrics"), "ragged_dot_ms")
    share = harness.load_layer_metric(
        os.path.join(harness.HERE, "layer_metrics"), "ragged_dot_roofline")
    monkeypatch.setattr(model_scopes, "counter", lambda run, name: held)
    for seconds, want in ((4 * least, 100.0), (16 * least, 25.0)):
        monkeypatch.setattr(model_scopes, "kernel", lambda run, name: {
            "s_per_step": seconds, "calls_per_step": 48.0}
            if name == "ragged-dot-none" else None)
        assert share.read(doctored_run()) == pytest.approx(want)
        assert ms.read(doctored_run()) == pytest.approx(1e3 * seconds)
    # no counter: nothing, whatever the trace holds
    monkeypatch.setattr(model_scopes, "counter", lambda run, name: None)
    assert share.read(doctored_run()) is None


def test_a_kernel_is_named_by_its_hlo_line():
    assert model_scopes.kernel_name(
        "%splash_mqa_fwd_residuals.3 = (f32[2,4,512,128]{3,2,1,0}) "
        "custom-call(%a)") == "splash_mqa_fwd_residuals"
    assert model_scopes.kernel_name(
        "%ragged-dot-none = bf16[8,8] custom-call(%a)") == "ragged-dot-none"
    assert model_scopes.kernel_name("%custom-call.12 = f32[8]{0} "
                                    "custom-call(%x)") == "custom-call"


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_finds_nothing_where_the_program_names_nothing(name):
    """An untraced run; and the recorded trace of a program from before
    the model (`testdata/tiny_sparse_4steps`): no scope of the model's, no
    kernel of its names, no counter: None, and nothing raises."""
    reader = harness.load_layer_metric(
        os.path.join(harness.HERE, "layer_metrics"), name)
    assert reader.read(doctored_run()) is None
    data = os.path.join(harness.HERE, "testdata")
    with open(os.path.join(data, "tiny_sparse_4steps.block.json")) as f:
        block = json.load(f)
    block["traced"] = True
    r = doctored_run(blocks={"sparse": [block]},
                     trace_dirs={"sparse": [data]},
                     trace={"arms": {"sparse": {"busy_s_per_step": 0.1}}})
    assert reader.read(r) is None
    got = model_scopes.reduced(r)        # the counters' readers never ask
    assert got is not None and got["scope_s_per_step"] == {}
    assert not any(k.startswith("splash") for k in got["kernels"])
