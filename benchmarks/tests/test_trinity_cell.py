"""The configuration `trinity_mini` (PR 40) through the harness at a tiny
size on the CPU, from a throw-away root that this file writes (files and
entries only; the reference and the readers are the real ones, found by
name), its operation and byte counts against direct counts, the accepted
readers it joins at its own keys (five attention layers of which four are
window layers, four expert layers with a shared expert), and its two new
readers on doctored runs, on the small trace recorded on the chip by
`record_trinity_scope_trace.py` (`testdata/tiny_trinity_scopes_4steps`) and
on the recorded trace of a program that names none of the model's scopes."""

import copy
import json
import os

import pytest

from benchmarks import (attn_ops, flops, gate_ops, harness, model_scopes,
                        moe_ops, scope_tree)
from test_harness_cpu import drive

CELL = "trinity_gated_dp1"
NEW_METRICS = ["attn_gate_ms", "attn_gate_roofline"]
SPLASH = {"splash_fwd_roofline": "splash_mqa_fwd_residuals",
          "splash_dq_roofline": "splash_mqa_dq_no_residuals",
          "splash_dkv_roofline": "splash_mqa_dkv_no_residuals"}
EXPERTS_ROOFLINE = "ragged_dot_roofline.moe_layers"
# the accepted readers that the cell joins beside those every cell lists
JOINED = ["attn_window_ms", "attn_full_ms", "attn_proj_ms", *SPLASH,
          "moe_experts_ms", "moe_router_ms", "moe_route_sort_ms",
          "moe_to_rows_ms", "moe_to_tokens_ms", "moe_product_glue_ms",
          "moe_shared_ms", "moe_load_max_over_mean", "moe_room_used",
          "ragged_dot_ms", EXPERTS_ROOFLINE, "dense_mlp_ms", "lm_head_ms",
          "rms_norm_ms", "fwd_bwd_unnamed_ms", "fwd_recomputed_ms",
          "sparse_mfu"]
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
RECORDED = "tiny_trinity_scopes_4steps"
TESTDATA = os.path.join(harness.HERE, "testdata")
SLIDING, FULL = "sliding_attention", "full_attention"


def real_config() -> dict:
    return harness.load_cell(CELL)["config_data"]


def reader(name):
    return harness.load_layer_metric(
        os.path.join(harness.HERE, "layer_metrics"), name)


def tiny_config() -> dict:
    """The real file with every size made tiny: what the reference reads
    (the published config's own keys) and what the trainer is given. The
    layers held are the real ones' kinds: sliding with the dense MLP, then
    sliding, full, sliding with experts."""
    cfg = copy.deepcopy(real_config())
    positions, vocab, steps = 32, 50, 6
    held = [0, 2, 3, 4]
    cfg.update(
        name="tiny_trinity", hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, intermediate_size=96,
        moe_intermediate_size=32, sliding_window=8, num_experts=4,
        n_routed_experts=4, num_experts_per_tok=2,
        num_hidden_layers=len(held), num_dense_layers=1, vocab_size=vocab,
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
            "num_kv_heads": 2, "head_dim": 16, "sliding_window": 8,
            "num_experts": 8, "experts_per_token": 2, "expert_width": 32,
            "expert_share": 1, "expert_shares": 2, "seq_len": positions})
    cfg["states"].update(compute_dtype="float32", kernel_mode="interpret")
    cfg["matmul_layers"] = [{"name": "lm_head", "positions": positions,
                             "k": 64, "n": vocab}]
    cfg["arch"].update(expert_product_macs_per_assignment=3 * 64 * 32,
                       attention_layers=4, gated_attention_layers=4,
                       expert_layers=3, sequence_length=positions)
    # float32 throughout: the sound runs read 1e-6 at most, the float8
    # control 1e-2 at least (tests/test_afmoe.py has both at this size)
    cfg["limits"].update(
        loss_gap_first=1e-4, loss_gap=1e-4, head_grad_rel_err=1e-3,
        grad_rel_err=1e-3, grad_norm_gap=1e-3, delta_norm_gap=1e-3,
        selected_over_k=[0.2, 200.0])
    return cfg


@pytest.fixture(scope="module")
def trinity_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny_trinity"))
    bdir = os.path.join(root, "benchmarks")
    for sub in ("configs", "traffic"):
        os.makedirs(os.path.join(bdir, sub))
    with open(os.path.join(bdir, "configs", "tiny_trinity.json"), "w") as f:
        json.dump(tiny_config(), f)
    mix = dict(harness.load_cell(CELL)["mix"], block_seconds=0.2)
    with open(os.path.join(bdir, "traffic", "dp1_sparse_blocks.json"),
              "w") as f:
        json.dump(mix, f)
    real = harness.load_benchmark()
    bench = {
        "command": real["command"], "paths": ["benchmarks"], "run_seconds": 1,
        "configs": [{"name": "tiny_trinity", "source": "throw-away",
                     "file": "benchmarks/configs/tiny_trinity.json",
                     "reduced": [], "why": "test"}],
        "workloads": [{"name": "tiny_gated", "config": "tiny_trinity",
                       "traffic": "dp1_sparse_blocks", "chips": 1,
                       "why": "test"}],
        "end_to_end": [m for m in real["end_to_end"]
                       if m["name"] != "dense_examples_per_s"],
        "per_layer": [dict(m, workloads=["tiny_gated"])
                      for m in real["per_layer"]
                      if CELL in m.get("workloads", ())]}
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_the_configuration_runs_end_to_end_on_the_cpu(trinity_root, capsys):
    rc, result, out = drive(trinity_root, capsys, "tiny_gated")
    assert rc == 0 and result["correct"] is True, out
    assert set(result["metrics"]) == {"examples_per_s", "step_ms_p95",
                                      "setup_s"}
    assert "sparse trainer built" in out and "dense trainer" not in out
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
        assert CELL in metrics[name]["workloads"], name
    listed = set(by_name(cell["per_layer"]))
    assert set(NEW_METRICS) | set(JOINED) <= listed
    # every metric that `lfm2_conv_dp1` lists but its mixer's own, this
    # cell lists too
    for m in bench["per_layer"]:
        if "lfm2_conv_dp1" in m.get("workloads", ()) \
                and not m["name"].startswith("short_conv"):
            assert m["name"] in listed, m["name"]
    # the readers that add up every Mosaic call of a step, the other
    # models' kernels and scopes, and the reader that divides the grouped
    # products by every layer
    assert not {"ef_select_ms", "ef_select_roofline", "dense_mfu",
                "exchange_ms", "attn_mla_ms", "mla_proj_ms",
                "mla_fwd_roofline", "ragged_dot_roofline", "short_conv_ms",
                "short_conv_gate_roofline"} & listed
    assert sum(1 for w in bench["workloads"] if w["chips"] == 4) == 1
    config = cell["config_data"]
    assert config["arch"]["num_params"] == 504147712
    assert [config[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "head_dim", "moe_intermediate_size", "intermediate_size",
        "num_experts_per_tok", "sliding_window", "route_scale",
        "num_shared_experts")] == [
        2048, 32, 4, 128, 1024, 6144, 8, 2048, 2.826, 1]
    assert config["published"]["num_experts"] == 128
    entry = by_name(bench["configs"])[cell["config"]]
    assert entry["reduced"] == list(config["reduced"]) == [
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size",
        "nworkers"]
    assert entry["source"] == config["source"]
    assert set(config["model_scopes"]) == {
        "attn_window", "attn_full", "attn_gate", "moe_router", "moe_experts",
        "moe_shared", "dense_mlp", "lm_head"}
    # a name that another reader takes whole stays off the list: the
    # innermost LISTED name wins, and `attn_proj_ms` reads `qk_norm` and
    # `rope` inside it (and `attn_gate`, which IS listed: both read it)
    assert not {"attn_proj", "qk_norm", "rope", "rms_norm"} & set(
        config["model_scopes"])
    assert config["head_leaf"] == "lm_head"
    for key in ("source", "deployment", "published", "share", "reduced",
                "assumed", "limits", "limits_read_from"):
        assert config[key], key
    assert str(config["arch"]["sequence_length"]) in cell["why"]


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
        "num_hidden_layers": 32, "num_dense_layers": 2, "num_experts": 128,
        "vocab_size": 200192} == {k: config["published"][k] for k in (
            "num_hidden_layers", "num_dense_layers", "num_experts",
            "vocab_size")}
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["vocab_size"]) == (5, 1, 8, 25024)
    assert config["vocab_size"] * 8 == 200192
    # the layers held are one whole period after the leading dense layer,
    # and the kinds `attn_ops.layer_pairs` counts are theirs
    kinds = [config["layer_types"][i] for i in config["share"]["layers"]]
    assert kinds == [SLIDING, SLIDING, FULL, SLIDING, SLIDING]
    assert kinds == config["trainer"]["model_kwargs"]["layer_types"]
    assert sorted(config["layer_types"][:5]) == sorted(kinds)


def test_the_counted_operations_are_the_issues():
    """356 M multiply-adds a token forward: the five projections of five
    attentions 38 %, the attention kernels 26 %, the head 14 %, the dense
    MLP 11 %, the shared experts 7 %, the held experts 4 %; 35 TFLOP a step
    counted; gated attention, projections, gate's product and kernels, is
    64 % of it."""
    config = real_config()
    s = config["arch"]["sequence_length"]
    tokens, held = 2 * s, 4 * 2 * s * 8 / 16
    per_expert = config["arch"]["expert_product_macs_per_assignment"]
    assert per_expert == 3 * 2048 * 1024
    macs = (flops.forward_macs_per_example(config) * 2 + per_expert * held)

    def share(*names):
        return 2 * sum(l["positions"] * l["k"] * l["n"]
                       for l in config["matmul_layers"]
                       if l["name"].split(".")[-1] in names) / macs

    proj = share("q_proj", "k_proj", "v_proj", "gate_proj", "o_proj")
    kernels = share("scores", "values")
    assert proj * macs / tokens == pytest.approx(136.3e6, rel=1e-3)
    assert share("gate_proj") * macs / tokens == 5 * 2048 * 4096
    assert share("mlp_w1", "mlp_w3", "mlp_w2") * macs / tokens == 37748736
    assert share("shared_w1", "shared_w3", "shared_w2") * macs / tokens \
        == 4 * 6291456
    assert share("lm_head") * macs / tokens == 2048 * 25024
    assert per_expert * held / tokens == pytest.approx(12.58e6, rel=1e-3)
    if s == 8192:
        assert kernels * macs / 2 == pytest.approx(756e9, rel=1e-3)
        assert macs / tokens == pytest.approx(356.4e6, rel=1e-3)
        assert proj + kernels == pytest.approx(0.64, abs=0.005)
        assert 6 * macs == pytest.approx(35.0e12, rel=2e-3)
    # the pairs are the masks' own
    window, full = (attn_ops.pairs(s, 2048), attn_ops.pairs(s))
    assert config["arch"]["attention_pairs"] == {SLIDING: window, FULL: full}
    assert attn_ops.layer_pairs(config) == [window] * 3 + [full, window]
    assert sorted(l["positions"] for l in config["matmul_layers"]
                  if l["name"].endswith(".scores")) == sorted(
        attn_ops.layer_pairs(config))


def doctored_run(**over):
    config = real_config()
    r = {"config": config, "cell": {"chips": 1}, "peaks": V5E,
         "mix": {"nworkers": 1}, "global_batch": {"sparse": 2},
         "blocks": {"sparse": []}, "trace": None}
    r.update(over)
    return r


def test_the_gates_bytes_against_a_direct_count(monkeypatch):
    """Five gated layers over 2 sequences of 32 heads of 128: output and
    gate in and the product out forward (twice where the layer is
    recomputed), the cotangent, output and gate in and two cotangents out
    backward, two bytes each."""
    run = doctored_run()
    s = run["config"]["arch"]["sequence_length"]
    elements = 5 * 2 * s * 4096
    assert gate_ops.elements_per_step(run) == elements
    monkeypatch.setattr(scope_tree, "pass_ms", lambda run, which: 120.0)
    assert gate_ops.gate_bytes_per_step(run) == elements * 2 * (
        (2 + 1) * 2 + (3 + 2))
    monkeypatch.setattr(scope_tree, "pass_ms", lambda run, which: None)
    assert gate_ops.gate_bytes_per_step(run) == elements * 2 * (3 + 5)
    other = copy.deepcopy(run["config"])
    del other["arch"]["gated_attention_layers"]
    assert gate_ops.gate_bytes_per_step(doctored_run(config=other)) is None
    # no accepted configuration has the key: their cells read nothing
    for name in ("mellum2_moe_dp1", "joyai_mla_dp1", "lfm2_conv_dp1"):
        arch = harness.load_cell(name)["config_data"]["arch"]
        assert "gated_attention_layers" not in arch


def test_the_gates_share_is_at_most_100(monkeypatch):
    """At the least time the passes could take it reads 100, at any longer
    time less."""
    monkeypatch.setattr(scope_tree, "pass_ms", lambda run, which: 120.0)
    s = real_config()["arch"]["sequence_length"]
    least_ms = 1e3 * 5 * 2 * s * 4096 * 22 / 819e9
    if s == 8192:
        assert least_ms == pytest.approx(9.013, rel=1e-3)
    share = reader("attn_gate_roofline")
    for ms, want in ((least_ms, 100.0), (2 * least_ms, 50.0),
                     (10 * least_ms, 10.0)):
        monkeypatch.setattr(
            model_scopes, "scope_ms",
            lambda run, name: ms if name == "attn_gate" else None)
        assert reader("attn_gate_ms").read(doctored_run()) == ms
        got = share.read(doctored_run())
        assert got == pytest.approx(want) and got <= 100.0 + 1e-9
    monkeypatch.setattr(model_scopes, "scope_ms", lambda run, name: None)
    assert share.read(doctored_run()) is None
    assert reader("attn_gate_ms").read(doctored_run()) is None


@pytest.mark.parametrize("name", list(SPLASH))
def test_an_attention_kernels_share_over_four_window_layers_and_a_full_one(
        monkeypatch, name):
    """`attn_ops.py` counts the pairs of `layer_types[:5]`, four window
    layers and one full as the held ones are, at heads of 128: with one
    call a key/value head, sequence, layer and pass (40 forward, 40 more
    recomputed) the share is those pairs' operations over the time, and at
    most 100."""
    config = real_config()
    kernel = SPLASH[name]
    s = config["arch"]["sequence_length"]
    pairs = 4 * attn_ops.pairs(s, 2048) + attn_ops.pairs(s)
    products = attn_ops.PRODUCTS[kernel]
    flop = products * 2 * 128 * 32 * pairs * 2
    assert attn_ops.flops_per_pass(config, kernel, 2) == flop
    least = flop / 197e12
    assert attn_ops.bytes_per_pass(config, kernel, 2) / 819e9 < least
    for passes, seconds, want in ((1, least, 100.0), (2, 2 * least, 100.0),
                                  (2, 5 * least, 40.0)):
        monkeypatch.setattr(model_scopes, "kernel", lambda run, k: {
            "s_per_step": seconds, "calls_per_step": 5.0 * passes}
            if k == kernel else None)
        got = reader(name).read(doctored_run())
        assert got == pytest.approx(want) and got <= 100.0 + 1e-9


def test_the_grouped_products_share_counts_the_layers_that_have_experts(
        monkeypatch):
    """24 calls a step over FOUR expert layers are 2 passes of 3 products;
    the reader takes the experts held from `n_routed_experts`, which this
    file carries as a second name of `num_experts`. At 1024 rows an expert
    the experts' own bytes are near the operations: the larger bounds."""
    config = real_config()
    assert config["n_routed_experts"] == config["num_experts"] == 8
    share = reader(EXPERTS_ROOFLINE)
    macs = config["arch"]["expert_product_macs_per_assignment"]
    held = 4 * 2 * config["arch"]["sequence_length"] * 8 / 16.0
    least = max(2.0 * macs * held / 197e12,
                2.0 * (held * 3 * (2048 + 1024) + 4 * 8 * macs) / 819e9)
    monkeypatch.setattr(model_scopes, "counter", lambda run, name: held)
    for seconds, want in ((2 * least, 100.0), (8 * least, 25.0)):
        monkeypatch.setattr(model_scopes, "kernel", lambda run, name: {
            "s_per_step": seconds, "calls_per_step": 24.0}
            if name == moe_ops.KERNEL else None)
        got = share.read(doctored_run())
        assert got == pytest.approx(want) and got <= 100.0 + 1e-9


def test_sparse_mfu_cannot_pass_100_in_this_cell(monkeypatch):
    config = real_config()
    s = config["arch"]["sequence_length"]
    held = 4 * 2 * s * 8 / 16.0     # an even load: 8 of 128 held, 4 layers
    monkeypatch.setattr(model_scopes, "counter", lambda run, name: held)
    need = (flops.train_flops_per_step(config, 2) + 6 * config["arch"][
        "expert_product_macs_per_assignment"] * held)
    if s == 8192:
        assert need == pytest.approx(35.0e12, rel=2e-3)
    least = need / 197e12
    for busy in (least, 3 * least):
        got = reader("sparse_mfu").read(doctored_run(trace={"arms": {
            "sparse": {"busy_s_per_step": busy}}}))
        assert got == pytest.approx(100.0 * least / busy) and got <= 100.0


STEP = "jit(sparse_step_fn)/fwd_bwd/"
BACK = STEP + "transpose(jvp(Afmoe))/fwd_bwd/jvp(Afmoe)/checkpoint/"


@pytest.mark.parametrize("tf_op,scope", [
    (STEP + "jvp(Afmoe)/layers_0/attn/attn_proj/attn_gate/mul", "attn_gate"),
    (BACK + "layers_3/attn/attn_proj/attn_gate/convert_element_type",
     "attn_gate"),
    (BACK + "rematted_computation/layers_2/attn/attn_proj/attn_gate/"
     "logistic", "attn_gate"),
    (STEP + "jvp(Afmoe)/layers_2/attn/attn_full/custom_vjp_call",
     "attn_full"),
    (STEP + "jvp(Afmoe)/layers_1/attn/attn_window/custom_vjp_call",
     "attn_window"),
    # `attn_proj`, `qk_norm` and `rope` are not on the configuration's
    # list: `scope_tree` reads the first, with the others inside it
    (STEP + "jvp(Afmoe)/layers_1/attn/attn_proj/gate_proj/dot_general", None),
    (STEP + "jvp(Afmoe)/layers_1/attn/attn_proj/qk_norm/mul", None),
    (STEP + "jvp(Afmoe)/layers_0/mlp/dense_mlp/dot_general", "dense_mlp"),
    (STEP + "jvp(Afmoe)/layers_2/moe/shared/moe_shared/dot_general",
     "moe_shared"),
    (STEP + "jvp(Afmoe)/layers_2/moe/moe_router/top_k", "moe_router"),
    (STEP + "jvp(Afmoe)/lm_head/dot_general", "lm_head"),
    (STEP + "jvp(Afmoe)/layers_2/post_mlp_norm/rms_norm/mul", None),
    (STEP + "jvp(Afmoe)/layers_2/add", None)])
def test_the_innermost_model_scope_of_an_op_name(tf_op, scope):
    assert model_scopes.scope_of(
        tf_op, real_config()["model_scopes"]) == scope


def test_the_gate_is_read_inside_the_projections_by_the_whole_path():
    """`scope_tree.MODEL_NAMES` is older than the gate: an operation under
    `attn_gate` reads as `attn_proj`'s, on its own pass, so `attn_proj_ms`
    holds `attn_gate_ms` and `fwd_bwd_unnamed_ms` none of it."""
    chain, which, _ = scope_tree.parse(
        BACK + "rematted_computation/layers_1/attn/attn_proj/attn_gate/mul:")
    assert chain == ("fwd_bwd", "attn_proj") and which == "recomputed"
    chain, which, _ = scope_tree.parse(
        BACK + "layers_1/attn/attn_proj/attn_gate/mul:")
    assert chain == ("fwd_bwd", "attn_proj") and which == "backward"
    chain, _, _ = scope_tree.parse(
        STEP + "jvp(Afmoe)/layers_1/pre_mlp_norm/rms_norm/mul:")
    assert chain == ("fwd_bwd", "rms_norm")


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
                    "(record_trinity_scope_trace.py)")
    for module in (model_scopes, scope_tree, trace_reduce):
        monkeypatch.setattr(module, "find_xplanes", lambda d: [path],
                            raising=False)
    return path


def test_the_new_readers_on_the_recorded_trace(only_the_recording):
    """A TPU's trace of the tiny model's sparse step: the gate's scope
    carries time in it on every pass, less than the projections' scope
    that holds it, and its share of the roofline is a share."""
    run = recorded_run()
    gate = reader("attn_gate_ms").read(run)
    proj = reader("attn_proj_ms").read(run)
    assert 0 < gate < proj
    share = reader("attn_gate_roofline").read(run)
    assert share is not None and 0 < share < 100
    tokens = 2 * 32
    assert gate_ops.gate_bytes_per_step(run) == 4 * tokens * 64 * 22
    # the accepted readers the cell joins find their scopes in it too
    for name in ("attn_window_ms", "attn_full_ms", "moe_experts_ms",
                 "moe_router_ms", "moe_shared_ms", "dense_mlp_ms",
                 "lm_head_ms", "rms_norm_ms", "fwd_recomputed_ms",
                 "fwd_bwd_unnamed_ms"):
        assert reader(name).read(run) > 0, name
    # `attn_gate` is no name of `scope_tree`'s: nothing of it is unnamed
    tree = scope_tree.reduced(run)
    assert not any("attn_gate" in tail for tail in tree["unnamed"])


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
