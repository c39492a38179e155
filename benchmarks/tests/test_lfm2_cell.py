"""The configuration `lfm2_8b_a1b` (PR 38) through the harness at a tiny
size on the CPU, from a throw-away root that this file writes (files and
entries only; the reference and the readers are the real ones, found by
name), its operation and byte counts against direct counts, the accepted
readers it joins at its own keys (heads of 64, one attention layer of five,
four expert layers), and its new readers on doctored runs, on the small
trace recorded on the chip by `record_lfm2_scope_trace.py`
(`testdata/tiny_lfm2_scopes_4steps`) and on the recorded trace of a program
that names none of the model's scopes."""

import copy
import json
import os

import pytest

from benchmarks import (attn_ops, conv_ops, flops, harness, model_scopes,
                        moe_ops, scope_tree)
from test_harness_cpu import drive

CELL = "lfm2_conv_dp1"
NEW_METRICS = ["short_conv_ms", "short_conv_proj_ms", "short_conv_gate_ms",
               "short_conv_gate_roofline"]
SPLASH = {"splash_fwd_roofline": "splash_mqa_fwd_residuals",
          "splash_dq_roofline": "splash_mqa_dq_no_residuals",
          "splash_dkv_roofline": "splash_mqa_dkv_no_residuals"}
EXPERTS_ROOFLINE = "ragged_dot_roofline.moe_layers"
# the accepted readers that the cell joins beside those every cell lists
JOINED = ["attn_full_ms", "attn_proj_ms", *SPLASH, "moe_experts_ms",
          "moe_router_ms", "moe_route_sort_ms", "moe_to_rows_ms",
          "moe_to_tokens_ms", "moe_product_glue_ms",
          "moe_load_max_over_mean", "ragged_dot_ms", EXPERTS_ROOFLINE,
          "dense_mlp_ms", "lm_head_ms", "rms_norm_ms", "fwd_bwd_unnamed_ms",
          "fwd_recomputed_ms", "sparse_mfu"]
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
RECORDED = "tiny_lfm2_scopes_4steps"
TESTDATA = os.path.join(harness.HERE, "testdata")


def real_config() -> dict:
    return harness.load_cell(CELL)["config_data"]


def reader(name):
    return harness.load_layer_metric(
        os.path.join(harness.HERE, "layer_metrics"), name)


def tiny_config() -> dict:
    """The real file with every size made tiny: what the reference reads
    (the published config's own keys) and what the trainer is given. The
    layers held are the real ones' kinds: conv with the dense MLP, then
    full_attention, conv, conv with experts."""
    cfg = copy.deepcopy(real_config())
    positions, vocab, steps = 32, 50, 6
    held = [0, 2, 3, 4]
    cfg.update(
        name="tiny_lfm2", hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, intermediate_size=96,
        moe_intermediate_size=32, num_experts=4, n_routed_experts=4,
        num_experts_per_tok=2, num_hidden_layers=len(held),
        num_dense_layers=1, vocab_size=vocab,
        published={"num_experts": 8},
        share={"expert_share": 1, "expert_shares": 2, "layers": held},
        examples_per_worker=steps,
        dataset_kwargs={"vocab_size": vocab, "bptt": positions},
        dataset_kwargs_per_worker={
            "synthetic_tokens_n": 2 * (positions * steps + 1)})
    cfg["trainer"].update(
        compute_dtype="float32", wire="off", density=0.01,
        model_kwargs={
            "hidden_size": 64, "num_layers": len(held),
            "layer_types": [cfg["layer_types"][i] for i in held],
            "num_dense_layers": 1, "dense_width": 96, "num_heads": 4,
            "num_kv_heads": 2, "head_dim": 16, "num_experts": 8,
            "experts_per_token": 2, "expert_width": 32, "expert_share": 1,
            "expert_shares": 2, "seq_len": positions})
    cfg["states"].update(compute_dtype="float32", kernel_mode="interpret")
    cfg["matmul_layers"] = [{"name": "lm_head", "positions": positions,
                             "k": 64, "n": vocab}]
    cfg["arch"].update(expert_product_macs_per_assignment=3 * 64 * 32,
                       attention_layers=1, conv_layers=3, expert_layers=3,
                       sequence_length=positions)
    # float32 throughout: the sound runs read 1e-6 at most, the float8
    # control 1e-2 at least (tests/test_lfm2_moe.py has both at this size)
    cfg["limits"].update(
        loss_gap_first=1e-4, loss_gap=1e-4, head_grad_rel_err=1e-3,
        grad_rel_err=1e-3, grad_norm_gap=1e-3, delta_norm_gap=1e-3,
        selected_over_k=[0.2, 200.0])
    return cfg


@pytest.fixture(scope="module")
def lfm2_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny_lfm2"))
    bdir = os.path.join(root, "benchmarks")
    for sub in ("configs", "traffic"):
        os.makedirs(os.path.join(bdir, sub))
    with open(os.path.join(bdir, "configs", "tiny_lfm2.json"), "w") as f:
        json.dump(tiny_config(), f)
    mix = dict(harness.load_cell(CELL)["mix"], block_seconds=0.2)
    with open(os.path.join(bdir, "traffic", "dp1_sparse_blocks.json"),
              "w") as f:
        json.dump(mix, f)
    real = harness.load_benchmark()
    bench = {
        "command": real["command"], "paths": ["benchmarks"], "run_seconds": 1,
        "configs": [{"name": "tiny_lfm2", "source": "throw-away",
                     "file": "benchmarks/configs/tiny_lfm2.json",
                     "reduced": [], "why": "test"}],
        "workloads": [{"name": "tiny_conv", "config": "tiny_lfm2",
                       "traffic": "dp1_sparse_blocks", "chips": 1,
                       "why": "test"}],
        "end_to_end": [m for m in real["end_to_end"]
                       if m["name"] != "dense_examples_per_s"],
        "per_layer": [dict(m, workloads=["tiny_conv"])
                      for m in real["per_layer"]
                      if CELL in m.get("workloads", ())]}
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_the_configuration_runs_end_to_end_on_the_cpu(lfm2_root, capsys):
    rc, result, out = drive(lfm2_root, capsys, "tiny_conv")
    assert rc == 0 and result["correct"] is True, out
    assert set(result["metrics"]) == {"examples_per_s", "step_ms_p95",
                                      "setup_s"}
    assert "sparse trainer built" in out and "dense trainer" not in out
    # the leaf nearest the loss is the tied one: it is compared by name
    assert result["check"]["head_grad_rel_err"]["value"] < 1e-4
    assert result["check"]["grad_rel_err"]["value"] < 1e-4
    assert result["check"]["lost"]["value"] == 0
    assert result["failed"] == 0 and result["attempted"] > 0


def by_name(entries):
    return {e["name"]: e for e in entries}


def test_the_real_benchmark_has_the_cell_and_its_readers():
    """Every entry is found by its `name`, never by its place."""
    bench = harness.load_benchmark()
    cell = harness.load_cell(CELL)
    assert cell["arms"] == ["sparse"] and cell["chips"] == 1
    assert cell["traffic"] == "dp1_sparse_blocks"
    assert [m["name"] for m in cell["end_to_end"]] == [
        "examples_per_s", "step_ms_p95", "setup_s"]
    metrics = by_name(bench["per_layer"])
    for name in NEW_METRICS:
        m = metrics[name]
        assert m["workloads"] == [CELL] and m["moves"] == "examples_per_s"
        assert m["source"] == "device_trace" and m["layer"] == "model"
        assert m["unit"] == ("%" if "roofline" in name else "ms")
        assert hasattr(reader(name), "read")
    for name in JOINED:
        assert metrics[name]["workloads"][-1] == CELL, name
    listed = set(by_name(cell["per_layer"]))
    assert set(NEW_METRICS) | set(JOINED) <= listed
    # every metric all five older cells list, this cell lists too
    older = [w["name"] for w in bench["workloads"] if w["name"] != CELL]
    for m in bench["per_layer"]:
        if set(older) <= set(m.get("workloads", older)):
            assert m["name"] in listed, m["name"]
    # the readers that add up every Mosaic call of a step, the other
    # models' kernels and scopes, and the reader that divides the grouped
    # products by every layer
    assert not {"ef_select_ms", "ef_select_roofline", "dense_mfu",
                "exchange_ms", "attn_window_ms", "attn_mla_ms",
                "mla_proj_ms", "moe_shared_ms", "mla_fwd_roofline",
                "ragged_dot_roofline"} & listed
    assert sum(1 for w in bench["workloads"] if w["chips"] == 4) == 1
    assert len(bench["configs"]) == 5 and len(bench["workloads"]) == 6
    config = cell["config_data"]
    assert config["arch"]["num_params"] == 507820288
    assert [config[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "moe_intermediate_size", "intermediate_size",
        "num_experts_per_tok", "conv_L_cache", "routed_scaling_factor")] == [
        2048, 32, 8, 64, 1792, 7168, 4, 3, 1]
    assert config["published"]["num_experts"] == 32
    entry = by_name(bench["configs"])[cell["config"]]
    assert entry["reduced"] == list(config["reduced"]) == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size",
        "nworkers"]
    assert set(config["model_scopes"]) >= set(conv_ops.SCOPES) | {
        "attn_full", "moe_router", "moe_experts", "dense_mlp", "lm_head"}
    # a name that another reader takes whole stays off the list: the
    # innermost LISTED name wins, and `attn_proj_ms` reads `qk_norm` inside
    assert not {"attn_proj", "qk_norm", "rms_norm"} & set(
        config["model_scopes"])
    assert config["head_leaf"] == "embed/embedding"
    for key in ("source", "published", "share", "reduced", "assumed",
                "limits", "limits_read_from"):
        assert config[key], key


def test_every_catalog_number_is_the_published_one_or_listed_as_reduced():
    """The catalog beside the `model-configs` guide, where it is installed:
    every key of the row's `config` is in the file, equal or reduced."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    config = real_config()
    with open(path) as f:
        row = [r for r in map(json.loads, f)
               if r["source_url"] == config["source"]][0]
    for key, value in row["config"].items():
        assert key in config, key
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert {k: row["config"][k] for k in config["reduced"]
            if k in row["config"]} == {
        "num_hidden_layers": 24, "num_dense_layers": 2, "num_experts": 32,
        "vocab_size": 65536}
    assert {k: config["published"][k] for k in (
        "num_hidden_layers", "num_dense_layers", "num_experts",
        "vocab_size")} == {"num_hidden_layers": 24, "num_dense_layers": 2,
                           "num_experts": 32, "vocab_size": 65536}
    # the layers held are one whole period after the leading dense layer
    kinds = [config["layer_types"][i] for i in config["share"]["layers"]]
    assert kinds == ["conv", "full_attention", "conv", "conv", "conv"]
    assert kinds == config["trainer"]["model_kwargs"]["layer_types"]


def test_the_counted_operations_are_the_issues():
    """216 M multiply-adds a token forward: the convolution mixer 31 %, the
    dense MLP and the held experts 20 % each, the head 16 %, attention
    13 %; 21.3 TFLOP a step counted (28 with the recomputation)."""
    config = real_config()
    tokens, held = 16384, 4 * 16384 * 4 / 4
    per_expert = config["arch"]["expert_product_macs_per_assignment"]
    assert per_expert == 3 * 2048 * 1792
    macs = (flops.forward_macs_per_example(config) * 2 + per_expert * held)
    assert macs / tokens == pytest.approx(216.3e6, rel=1e-3)

    def share(*names):
        return 2 * sum(l["positions"] * l["k"] * l["n"]
                       for l in config["matmul_layers"]
                       if l["name"].split(".")[-1] in names) / macs

    assert share("conv_in_proj", "conv_out_proj") == pytest.approx(
        0.31, abs=0.005)
    assert share("mlp_w1", "mlp_w3", "mlp_w2") == pytest.approx(
        0.204, abs=0.005)
    assert per_expert * held / macs == pytest.approx(0.204, abs=0.005)
    assert share("lm_head") == pytest.approx(0.155, abs=0.005)
    assert share("q_proj", "k_proj", "v_proj", "o_proj", "scores",
                 "values") == pytest.approx(0.126, abs=0.005)
    assert 6 * macs == pytest.approx(21.26e12, rel=2e-3)
    # the taps and the gates are not among them: 10 240 a token a layer
    # against 16.8 M in the layer's two products
    conv = [l for l in config["matmul_layers"] if "conv_" in l["name"]]
    assert len(conv) == 2 * config["arch"]["conv_layers"] == 8
    assert sum(l["k"] * l["n"] for l in conv) == 4 * 16777216


def doctored_run(**over):
    config = real_config()
    r = {"config": config, "cell": {"chips": 1}, "peaks": V5E,
         "mix": {"nworkers": 1}, "global_batch": {"sparse": 2},
         "blocks": {"sparse": []}, "trace": None}
    r.update(over)
    return r


def test_the_gates_bytes_against_a_direct_count(monkeypatch):
    """Four conv layers over 2 x 8192 tokens of 2048 channels: B, C, x in
    and y out forward (twice where the layer is recomputed), the cotangent
    and B, C, x in and three cotangents out backward, two bytes each."""
    run = doctored_run()
    elements = 4 * 2 * 8192 * 2048
    assert conv_ops.elements_per_step(run) == elements
    monkeypatch.setattr(scope_tree, "pass_ms", lambda run, which: 120.0)
    assert conv_ops.gate_bytes_per_step(run) == elements * 2 * (
        (3 + 1) * 2 + (4 + 3))
    monkeypatch.setattr(scope_tree, "pass_ms", lambda run, which: None)
    assert conv_ops.gate_bytes_per_step(run) == elements * 2 * (4 + 7)
    monkeypatch.setattr(scope_tree, "pass_ms", lambda run, which: 120.0)
    # bound by memory, far from the ridge
    assert (conv_ops.gate_ops_per_step(run) / 197e12
            < 0.01 * conv_ops.gate_bytes_per_step(run) / 819e9)
    other = copy.deepcopy(run["config"])
    del other["arch"]["conv_layers"]
    assert conv_ops.gate_bytes_per_step(doctored_run(config=other)) is None


def test_the_gates_share_is_at_most_100(monkeypatch):
    """At the least time the passes could take it reads 100, at any longer
    time less."""
    monkeypatch.setattr(scope_tree, "pass_ms", lambda run, which: 120.0)
    least_ms = 1e3 * 4 * 2 * 8192 * 2048 * 30 / 819e9
    assert least_ms == pytest.approx(4.916, rel=1e-3)
    share = reader("short_conv_gate_roofline")
    for ms, want in ((least_ms, 100.0), (2 * least_ms, 50.0),
                     (10 * least_ms, 10.0)):
        monkeypatch.setattr(
            model_scopes, "scope_ms",
            lambda run, name: ms if name == "conv_gate" else None)
        got = share.read(doctored_run())
        assert got == pytest.approx(want) and got <= 100.0 + 1e-9
    monkeypatch.setattr(model_scopes, "scope_ms", lambda run, name: None)
    assert share.read(doctored_run()) is None


def test_the_mixers_readers_add_up(monkeypatch):
    times = {"short_conv": 0.5, "conv_in_proj": 60.0, "conv_gate": 9.0,
             "conv_out_proj": 20.0, "attn_full": 40.0}
    monkeypatch.setattr(model_scopes, "scope_ms",
                        lambda run, name: times.get(name))
    run = doctored_run()
    whole = reader("short_conv_ms").read(run)
    assert whole == pytest.approx(89.5)
    assert reader("short_conv_proj_ms").read(run) == pytest.approx(80.0)
    assert reader("short_conv_gate_ms").read(run) == pytest.approx(9.0)
    assert whole == pytest.approx(
        reader("short_conv_proj_ms").read(run)
        + reader("short_conv_gate_ms").read(run) + times["short_conv"])


@pytest.mark.parametrize("name", list(SPLASH))
def test_an_attention_kernels_share_at_heads_of_64_in_one_layer_of_five(
        monkeypatch, name):
    """`attn_ops.py` takes every one of the configuration's
    `num_hidden_layers` for an attention layer, in the operations AND in
    the calls it divides by, so the two cancel: with the ONE attention
    layer's calls (one a key/value head and sequence and pass: 16 forward,
    16 more recomputed) the share is the causal pairs' operations at heads
    of 64 over the time, and at most 100."""
    config = real_config()
    kernel = SPLASH[name]
    pairs = attn_ops.pairs(8192)
    assert pairs == config["arch"]["attention_pairs"]["causal"]
    products = attn_ops.PRODUCTS[kernel]
    # one layer, one pass, two sequences: 32 heads of 64
    flop = products * 2 * 64 * 32 * pairs * 2
    assert attn_ops.flops_per_pass(config, kernel, 2) == 5 * flop
    least = flop / 197e12
    assert attn_ops.bytes_per_pass(config, kernel, 2) / 5 / 819e9 < least
    for passes, seconds, want in ((1, least, 100.0), (2, 2 * least, 100.0),
                                  (2, 5 * least, 40.0)):
        monkeypatch.setattr(model_scopes, "kernel", lambda run, k: {
            "s_per_step": seconds, "calls_per_step": float(passes)}
            if k == kernel else None)
        got = reader(name).read(doctored_run())
        assert got == pytest.approx(want) and got <= 100.0 + 1e-9


def test_the_grouped_products_share_counts_the_layers_that_have_experts(
        monkeypatch):
    """24 calls a step over FOUR expert layers are 2 passes of 3 products
    (`moe_ops` would divide by the five layers); the reader takes the
    experts held from `n_routed_experts`, which this file carries as a
    second name of `num_experts`."""
    config = real_config()
    assert config["n_routed_experts"] == config["num_experts"] == 8
    share = reader(EXPERTS_ROOFLINE)
    macs = config["arch"]["expert_product_macs_per_assignment"]
    held = 4 * 16384.0
    least = 2.0 * macs * held / 197e12
    assert least > 2.0 * (held * 3 * (2048 + 1792) + 4 * 8 * macs) / 819e9
    monkeypatch.setattr(model_scopes, "counter", lambda run, name: held)
    for seconds, want in ((2 * least, 100.0), (8 * least, 25.0)):
        monkeypatch.setattr(model_scopes, "kernel", lambda run, name: {
            "s_per_step": seconds, "calls_per_step": 24.0}
            if name == moe_ops.KERNEL else None)
        got = share.read(doctored_run())
        assert got == pytest.approx(want) and got <= 100.0 + 1e-9


def test_sparse_mfu_cannot_pass_100_in_this_cell(monkeypatch):
    config = real_config()
    held = 4 * 16384.0      # an even load: a quarter of 4 a token, 4 layers
    monkeypatch.setattr(model_scopes, "counter", lambda run, name: held)
    need = (flops.train_flops_per_step(config, 2) + 6 * config["arch"][
        "expert_product_macs_per_assignment"] * held)
    assert need == pytest.approx(21.26e12, rel=2e-3)
    least = need / 197e12
    for busy in (least, 3 * least):
        got = reader("sparse_mfu").read(doctored_run(trace={"arms": {
            "sparse": {"busy_s_per_step": busy}}}))
        assert got == pytest.approx(100.0 * least / busy) and got <= 100.0


STEP = "jit(sparse_step_fn)/fwd_bwd/"
BACK = STEP + "transpose(jvp(LFM2MoE))/fwd_bwd/jvp(LFM2MoE)/checkpoint/"


@pytest.mark.parametrize("tf_op,scope", [
    (STEP + "jvp(LFM2MoE)/layers_0/conv/short_conv/conv_in_proj/in_proj/"
     "dot_general", "conv_in_proj"),
    (STEP + "jvp(LFM2MoE)/layers_3/conv/short_conv/conv_gate/"
     "custom_vjp_call/mul", "conv_gate"),
    (BACK + "layers_3/conv/short_conv/conv_gate/concatenate", "conv_gate"),
    (BACK + "rematted_computation/layers_2/conv/short_conv/conv_out_proj/"
     "out_proj/dot_general", "conv_out_proj"),
    (STEP + "jvp(LFM2MoE)/layers_2/conv/short_conv/reshape", "short_conv"),
    (STEP + "jvp(LFM2MoE)/layers_1/attn/attn_full/custom_vjp_call",
     "attn_full"),
    # `attn_proj` and `qk_norm` are not on the configuration's list:
    # `scope_tree` reads the first, with the second inside it
    (STEP + "jvp(LFM2MoE)/layers_1/attn/attn_proj/qk_norm/mul", None),
    (STEP + "jvp(LFM2MoE)/layers_0/mlp/dense_mlp/dot_general", "dense_mlp"),
    (STEP + "jvp(LFM2MoE)/layers_2/moe/moe_router/top_k", "moe_router"),
    (STEP + "jvp(LFM2MoE)/lm_head/bsh,vh->bsv/dot_general", "lm_head"),
    (STEP + "jvp(LFM2MoE)/layers_2/add", None)])
def test_the_innermost_model_scope_of_an_op_name(tf_op, scope):
    assert model_scopes.scope_of(
        tf_op, real_config()["model_scopes"]) == scope


def test_qk_norm_is_read_inside_the_projections():
    chain, which, _ = scope_tree.parse(
        BACK + "rematted_computation/layers_1/attn/attn_proj/qk_norm/mul:")
    assert chain == ("fwd_bwd", "attn_proj") and which == "recomputed"
    # `scope_tree.MODEL_NAMES` is older than the convolution mixer: what
    # lies under `short_conv` is under no name it knows, so in this cell
    # `fwd_bwd_unnamed_ms` holds `short_conv_ms` too (PERF.md section 7)
    chain, _, _ = scope_tree.parse(
        STEP + "jvp(LFM2MoE)/layers_0/conv/short_conv/conv_gate/mul:")
    assert chain == ("fwd_bwd",)


def recorded_run():
    with open(os.path.join(TESTDATA, RECORDED + ".block.json")) as f:
        block = json.load(f)
    block["traced"] = True
    return doctored_run(config=tiny_config(), blocks={"sparse": [block]},
                        trace_dirs={"sparse": [TESTDATA]},
                        trace={"arms": {"sparse": {"busy_s_per_step": 0.1}}})


@pytest.fixture()
def only_the_recording(monkeypatch):
    """`find_xplanes` takes every trace under the directory; the readers
    are given this recording alone."""
    from benchmarks import trace_reduce
    path = os.path.join(TESTDATA, RECORDED + ".xspace.pb")
    if not os.path.exists(path):
        pytest.skip("the recording is made on the chip "
                    "(record_lfm2_scope_trace.py)")
    for module in (model_scopes, scope_tree, trace_reduce):
        monkeypatch.setattr(module, "find_xplanes", lambda d: [path],
                            raising=False)
    return path


def test_the_new_readers_on_the_recorded_trace(only_the_recording):
    """A TPU's trace of the tiny model's sparse step: every scope of the
    mixer carries time in it, the parts add up to the whole, and the
    unnamed remainder that `scope_tree` reads holds the mixer."""
    run = recorded_run()
    whole = reader("short_conv_ms").read(run)
    proj = reader("short_conv_proj_ms").read(run)
    gate = reader("short_conv_gate_ms").read(run)
    assert whole > 0 and proj > 0 and gate > 0
    own = model_scopes.scope_ms(run, "short_conv") or 0.0
    assert whole == pytest.approx(proj + gate + own)
    share = reader("short_conv_gate_roofline").read(run)
    assert share is not None and 0 < share < 100
    unnamed = reader("fwd_bwd_unnamed_ms").read(run)
    assert unnamed >= whole
    # the accepted readers the cell joins find their scopes in it too
    for name in ("attn_full_ms", "attn_proj_ms", "moe_experts_ms",
                 "moe_router_ms", "dense_mlp_ms", "lm_head_ms",
                 "rms_norm_ms", "fwd_recomputed_ms"):
        assert reader(name).read(run) > 0, name


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_finds_nothing_where_the_program_names_nothing(name):
    """An untraced run; and the recorded trace of a program from before
    the model (`testdata/tiny_sparse_4steps`): no scope of the model's, no
    kernel of its names, no counter: None, and nothing raises."""
    assert reader(name).read(doctored_run()) is None
    with open(os.path.join(TESTDATA, "tiny_sparse_4steps.block.json")) as f:
        block = json.load(f)
    block["traced"] = True
    r = doctored_run(blocks={"sparse": [block]},
                     trace_dirs={"sparse": [TESTDATA]},
                     trace={"arms": {"sparse": {"busy_s_per_step": 0.1}}})
    assert reader(name).read(r) is None
