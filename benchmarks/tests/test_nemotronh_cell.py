"""The configuration `nemotron_twotower_30b_a3b` (PR 48) through the harness
at a tiny size on the CPU, from a throw-away root that this file writes
(files and entries only; the reference and the readers are the real ones,
found by name), its operation and byte counts against direct counts, the
accepted readers it joins at its own keys (one attention block at 32 heads
over 2 of 128 without positions; three expert blocks of two products an
expert), and its eight new readers on doctored runs, on the small trace
recorded on the chip by `record_nemotronh_scope_trace.py`
(`testdata/tiny_nemotronh_scopes_4steps`) and on the recorded trace of a
program that names none of the model's scopes."""

import copy
import json
import os

import pytest

from benchmarks import (attn_ops, flops, harness, model_scopes, scope_tree,
                        ssd_ops)
from test_harness_cpu import drive

CELL = "nemotronh_ssd_dp1"
MS = ["ssm_ms", "ssm_proj_ms", "ssm_conv_ms", "ssm_scan_ms",
      "ssm_norm_gate_ms"]
SHARES = {"ssm_scan_roofline": "ssm_scan", "ssm_conv_roofline": "ssm_conv",
          "ssm_norm_gate_roofline": "ssm_norm_gate"}
NEW_METRICS = MS + list(SHARES)
# the accepted readers that the cell joins beside those every cell lists
JOINED = ["attn_full_ms", "attn_proj_ms", "splash_fwd_roofline",
          "splash_dkv_roofline", "moe_experts_ms", "moe_router_ms",
          "moe_route_sort_ms", "moe_to_rows_ms", "moe_to_tokens_ms",
          "moe_product_glue_ms", "moe_shared_ms", "moe_load_max_over_mean",
          "moe_room_used", "lm_head_ms", "rms_norm_ms", "fwd_recomputed_ms",
          "sparse_mfu"]
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
RECORDED = "tiny_nemotronh_scopes_4steps"
TESTDATA = os.path.join(harness.HERE, "testdata")
PATTERN = "EMEMEM*"


def real_config() -> dict:
    return harness.load_cell(CELL)["config_data"]


def reader(name):
    return harness.load_layer_metric(
        os.path.join(harness.HERE, "layer_metrics"), name)


def tiny_config() -> dict:
    """The real file with every size made tiny: what the reference reads
    (the published config's own keys) and what the trainer is given. The
    blocks held are the real ones' kinds: E M E M E M *."""
    cfg = copy.deepcopy(real_config())
    positions, vocab, steps = 32, 50, 6
    cfg.update(
        name="tiny_nemotronh", hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, mamba_num_heads=4,
        mamba_head_dim=16, ssm_state_size=16, n_groups=2, chunk_size=8,
        moe_intermediate_size=32, moe_shared_expert_intermediate_size=48,
        n_routed_experts=4, num_experts_per_tok=2, vocab_size=vocab,
        published={"n_routed_experts": 8, "num_hidden_layers": 52},
        share={"expert_share": 1, "expert_shares": 2,
               "layers": [6, 7, 8, 9, 10, 11, 12]},
        examples_per_worker=steps,
        dataset_kwargs={"vocab_size": vocab, "bptt": positions},
        dataset_kwargs_per_worker={
            "synthetic_tokens_n": 2 * (positions * steps + 1)})
    cfg["trainer"].update(
        compute_dtype="float32", wire="off", density=0.01,
        model_kwargs={
            "hidden_size": 64, "pattern": PATTERN, "mamba_num_heads": 4,
            "mamba_head_dim": 16, "ssm_state_size": 16, "n_groups": 2,
            "chunk_size": 8, "num_heads": 4, "num_kv_heads": 2,
            "head_dim": 16, "num_experts": 8, "experts_per_token": 2,
            "expert_width": 32, "shared_expert_width": 48,
            "expert_share": 1, "expert_shares": 2, "seq_len": positions})
    cfg["states"].update(compute_dtype="float32", kernel_mode="interpret")
    cfg["matmul_layers"] = [{"name": "lm_head", "positions": positions,
                             "k": 64, "n": vocab}]
    cfg["arch"].update(expert_product_macs_per_assignment=2 * 64 * 32,
                       sequence_length=positions)
    # float32 throughout: the sound runs read 1e-6 at most but for the
    # decay's leaves of 4 entries, which only the scan's state reaches
    # (tests/test_nemotron_h.py has them at this size)
    cfg["limits"].update(
        loss_gap_first=1e-4, loss_gap=1e-4, head_grad_rel_err=1e-3,
        grad_rel_err=1e-3, grad_norm_gap=0.05, delta_norm_gap=1e-3,
        selected_over_k=[0.2, 200.0])
    return cfg


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny_nemotronh"))
    bdir = os.path.join(root, "benchmarks")
    for sub in ("configs", "traffic"):
        os.makedirs(os.path.join(bdir, sub))
    with open(os.path.join(bdir, "configs", "tiny_nemotronh.json"),
              "w") as f:
        json.dump(tiny_config(), f)
    mix = dict(harness.load_cell(CELL)["mix"], block_seconds=0.2)
    with open(os.path.join(bdir, "traffic", "dp1_sparse_blocks.json"),
              "w") as f:
        json.dump(mix, f)
    real = harness.load_benchmark()
    bench = {
        "command": real["command"], "paths": ["benchmarks"], "run_seconds": 1,
        "configs": [{"name": "tiny_nemotronh", "source": "throw-away",
                     "file": "benchmarks/configs/tiny_nemotronh.json",
                     "reduced": [], "why": "test"}],
        "workloads": [{"name": "tiny_ssd", "config": "tiny_nemotronh",
                       "traffic": "dp1_sparse_blocks", "chips": 1,
                       "why": "test"}],
        "end_to_end": [m for m in real["end_to_end"]
                       if m["name"] != "dense_examples_per_s"],
        "per_layer": [dict(m, workloads=["tiny_ssd"])
                      for m in real["per_layer"]
                      if CELL in m.get("workloads", ())]}
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_the_configuration_runs_end_to_end_on_the_cpu(tiny_root, capsys):
    rc, result, out = drive(tiny_root, capsys, "tiny_ssd")
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
    # every metric that the four older sibling cells list, this cell lists
    # too, but for the reader of a kernel that no cell has called since PR
    # 41, the dense layers' reader (the held blocks have none) and
    # `fwd_bwd_unnamed_ms`: `scope_tree.MODEL_NAMES` has none of the
    # mixer's names, so here it would read the mixers as unnamed
    four = {"mellum2_moe_dp1", "joyai_mla_dp1", "lfm2_conv_dp1",
            "trinity_gated_dp1"}
    for m in bench["per_layer"]:
        if four <= set(m.get("workloads", ())) and m["name"] not in (
                "ragged_dot_ms", "fwd_bwd_unnamed_ms", "dense_mlp_ms",
                "ragged_dot_roofline.moe_layers"):
            assert m["name"] in listed, m["name"]
    # the ledger's stale metrics, the other models' kernels and scopes, and
    # what this model has not got: a gate on its attention, a window, a
    # dense block
    assert not {"fwd_bwd_unnamed_ms", "ragged_dot_ms",
                "ragged_dot_roofline.moe_layers", "ragged_dot_roofline",
                "splash_dq_roofline", "mla_dq_roofline", "attn_window_ms",
                "attn_gate_ms", "attn_gate_roofline", "dense_mlp_ms",
                "ef_select_ms", "dense_mfu", "exchange_ms", "attn_mla_ms",
                "short_conv_ms", "linear_attn_ms", "gdn_rule_roofline"
                } & listed
    assert sum(1 for w in bench["workloads"] if w["chips"] == 4) == 1
    assert (len(bench["configs"]), len(bench["workloads"])) == (8, 9)
    config = cell["config_data"]
    assert config["arch"]["num_params"] == 528093120
    assert [config[k] for k in (
        "hidden_size", "mamba_num_heads", "mamba_head_dim", "ssm_state_size",
        "n_groups", "conv_kernel", "chunk_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "moe_intermediate_size",
        "moe_shared_expert_intermediate_size", "num_experts_per_tok",
        "routed_scaling_factor")] == [
        2688, 64, 64, 128, 8, 4, 128, 32, 2, 128, 1856, 3712, 6, 2.5]
    assert config["published"]["n_routed_experts"] == 128
    entry = by_name(bench["configs"])[cell["config"]]
    assert entry["reduced"] == list(config["reduced"]) == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size", "nworkers"]
    assert entry["source"] == config["source"]
    assert set(config["model_scopes"]) == set(ssd_ops.SCOPES) | {
        "attn_full", "moe_router", "moe_experts", "moe_shared", "lm_head"}
    # a name that another reader takes whole stays off the list: the
    # innermost LISTED name wins
    assert not {"attn_proj", "qk_norm", "rope", "rms_norm"} & set(
        config["model_scopes"])
    assert config["head_leaf"] == "lm_head"
    for key in ("source", "deployment", "published", "share", "reduced",
                "assumed", "limits", "limits_read_from"):
        assert config[key], key
    assert str(config["arch"]["sequence_length"]) in cell["why"]
    # the tower and the objective that are left out are named
    assert "LEFT OUT" in config["assumed"]["second_tower"]
    assert "LEFT OUT" in config["assumed"]["diffusion_objective"]


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
        "num_hidden_layers": 52, "n_routed_experts": 128,
        "vocab_size": 131072,
        "hybrid_override_pattern": config["published"][
            "hybrid_override_pattern"]} == {
        k: config["published"][k] for k in (
            "num_hidden_layers", "n_routed_experts", "vocab_size",
            "hybrid_override_pattern")}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (7, 8, 16384)
    assert config["vocab_size"] * 8 == 131072
    assert config["n_routed_experts"] * config["share"][
        "expert_shares"] == 128
    # the blocks held are one whole unit of the published pattern, which
    # repeats it four times; `layer_types` (a key the published config does
    # not carry) is the held pattern written out
    whole = config["published"]["hybrid_override_pattern"]
    held = "".join(whole[i] for i in config["share"]["layers"])
    assert held == PATTERN == config["hybrid_override_pattern"] \
        == config["trainer"]["model_kwargs"]["pattern"]
    assert whole[6:34] == PATTERN * 4 and len(whole) == 52
    assert config["layer_types"] == [
        {"M": "mamba", "E": "moe", "*": "attention"}[k] for k in PATTERN]
    # no width is cut: no key of a width is listed
    assert not [k for k in config["reduced"]
                if k.endswith(("_dim", "_size", "_rank", "_per_tok"))
                and k != "vocab_size"]


def test_the_counted_operations_are_the_issues():
    """292 M multiply-adds a token forward: the three state-space blocks 41
    % (their scan as the recurrence's two products of 128 x 64 a token and
    head), experts 25 %, the attention block 20 %, the head 15 %."""
    config = real_config()
    s = config["arch"]["sequence_length"]
    tokens, held = 2 * s, 3 * 2 * s * 6 / 16
    per_expert = config["arch"]["expert_product_macs_per_assignment"]
    assert per_expert == 2 * 2688 * 1856 == 9977856
    macs = (flops.forward_macs_per_example(config) * 2 + per_expert * held)

    def share(*names, layers="0123456"):
        return 2 * sum(l["positions"] * l["k"] * l["n"]
                       for l in config["matmul_layers"]
                       if l["name"].split(".")[-1] in names
                       and l["name"][1] in layers) / macs

    mamba = share("in_proj", "ssd", "out_proj")
    attention = share("q_proj", "k_proj", "v_proj", "scores", "values",
                      "o_proj")
    per_token = macs / tokens
    assert share("ssd") * per_token == pytest.approx(3 * 2 * 64 * 128 * 64)
    assert share("in_proj") * per_token == pytest.approx(
        3 * 2688 * (4096 + 6144 + 64))
    assert share("shared_w1", "shared_w2") * per_token == pytest.approx(
        3 * 2 * 2688 * 3712)
    assert share("lm_head", layers="m") * per_token == pytest.approx(
        2688 * 16384)
    assert held == 18432                        # an even load
    if s == 8192:
        assert macs / tokens == pytest.approx(292.4e6, rel=1e-3)
        assert mamba == pytest.approx(0.41, abs=0.005)
        assert attention == pytest.approx(0.195, abs=0.005)
        assert share("lm_head", layers="m") == pytest.approx(0.15, abs=0.005)
    # the attention block's pairs are the mask's own; `attn_ops` counts
    # every layer that is no window layer as a causal one and divides the
    # kernels' calls by `num_hidden_layers`: the seven cancel to the one
    assert config["arch"]["attention_pairs"] == {
        "full_attention": attn_ops.pairs(s)}
    assert attn_ops.layer_pairs(config) == [attn_ops.pairs(s)] * 7
    assert [l["positions"] for l in config["matmul_layers"]
            if l["name"].endswith(".scores")] == [attn_ops.pairs(s)]


def doctored_run(**over):
    config = real_config()
    r = {"config": config, "cell": {"chips": 1}, "peaks": V5E,
         "mix": {"nworkers": 1}, "global_batch": {"sparse": 2},
         "blocks": {"sparse": []}, "trace": None}
    r.update(over)
    return r


def test_the_mixers_counts_against_direct_counts(monkeypatch):
    """Three state-space blocks over 2 sequences: the scan's two products
    of 128 x 64 a token and head forward, twice that backward; x and y (64
    heads of 64), B and C (8 groups of 128) in bfloat16 and dt in float32."""
    run = doctored_run()
    s = run["config"]["arch"]["sequence_length"]
    tokens = 3 * 2 * s
    forward = 2 * 2 * 128 * 64 * 64
    read = 2 * (4096 + 1024 + 1024) + 4 * 64
    monkeypatch.setattr(scope_tree, "pass_ms", lambda run, which: 120.0)
    assert ssd_ops.scan_flops_per_step(run) == tokens * 4 * forward
    assert ssd_ops.scan_bytes_per_step(run) == tokens * (
        2 * (read + 2 * 4096) + (read + 2 * 4096 + read))
    monkeypatch.setattr(scope_tree, "pass_ms", lambda run, which: None)
    assert ssd_ops.scan_flops_per_step(run) == tokens * 3 * forward
    other = copy.deepcopy(run["config"])
    del other["arch"]["ssm_layers"]
    assert ssd_ops.scan_flops_per_step(doctored_run(config=other)) is None
    assert ssd_ops.scan_bytes_per_step(doctored_run(config=other)) is None
    # no accepted configuration has the key: their cells read nothing
    for name in ("mellum2_moe_dp1", "joyai_mla_dp1", "lfm2_conv_dp1",
                 "trinity_gated_dp1", "qwen3next_gdn_dp1"):
        arch = harness.load_cell(name)["config_data"]["arch"]
        assert "ssm_layers" not in arch


@pytest.mark.parametrize("name", list(SHARES))
def test_a_share_is_at_most_100(monkeypatch, name):
    """At the least time the passes could take it reads 100, at any longer
    time less; the scan is bound by its bytes."""
    monkeypatch.setattr(scope_tree, "pass_ms", lambda run, which: 120.0)
    run = doctored_run()
    s = run["config"]["arch"]["sequence_length"]
    tokens = 3 * 2 * s
    least_ms = 1e3 * {
        "ssm_scan": max(ssd_ops.scan_flops_per_step(run) / 197e12,
                        ssd_ops.scan_bytes_per_step(run) / 819e9),
        "ssm_conv": tokens * 6144 * (2 * 4 + 6) / 819e9,
        "ssm_norm_gate": tokens * 4096 * (2 * 6 + 10) / 819e9}[SHARES[name]]
    if s == 8192 and name == "ssm_scan_roofline":
        assert (ssd_ops.scan_bytes_per_step(run) / 819e9
                > ssd_ops.scan_flops_per_step(run) / 197e12)
    ms_reader = reader(SHARES[name] + "_ms")
    for ms, want in ((least_ms, 100.0), (2 * least_ms, 50.0),
                     (10 * least_ms, 10.0)):
        monkeypatch.setattr(
            model_scopes, "scope_ms",
            lambda run, scope: ms if scope == SHARES[name] else None)
        assert ms_reader.read(doctored_run()) == ms
        got = reader(name).read(doctored_run())
        assert got == pytest.approx(want) and got <= 100.0 + 1e-9
    monkeypatch.setattr(model_scopes, "scope_ms", lambda run, scope: None)
    assert reader(name).read(doctored_run()) is None
    assert ms_reader.read(doctored_run()) is None


def test_the_mixers_readers_add_up(monkeypatch):
    each = {"ssm": 0.5, "ssm_in_proj": 30.0, "ssm_conv": 20.0,
            "ssm_scan": 100.0, "ssm_norm_gate": 10.0, "ssm_out_proj": 9.0}
    monkeypatch.setattr(model_scopes, "scope_ms",
                        lambda run, scope: each.get(scope))
    run = doctored_run()
    assert reader("ssm_ms").read(run) == sum(each.values())
    assert reader("ssm_proj_ms").read(run) == 39.0
    assert reader("ssm_scan_ms").read(run) == 100.0


def test_the_accepted_readers_take_the_one_attention_block(monkeypatch):
    """`attn_ops` at 32 heads of 128 with the seven blocks' pairs over
    seven blocks' calls."""
    run = doctored_run()
    s = run["config"]["arch"]["sequence_length"]
    kernel = "splash_mqa_fwd_residuals"
    flop = 2 * 2 * 128 * 32 * attn_ops.pairs(s) * 2      # one block's
    assert attn_ops.flops_per_pass(run["config"], kernel, 2) == 7 * flop
    least = flop / 197e12
    # 2 sequences x 2 key/value heads calls a pass: forward and recomputed
    for calls, seconds, want in ((1.0, least, 100.0), (2.0, 4 * least, 50.0)):
        monkeypatch.setattr(model_scopes, "kernel", lambda run, k: {
            "s_per_step": seconds, "calls_per_step": calls}
            if k == kernel else None)
        got = reader("splash_fwd_roofline").read(doctored_run())
        assert got == pytest.approx(want) and got <= 100.0 + 1e-9


def test_sparse_mfu_cannot_pass_100_in_this_cell(monkeypatch):
    config = real_config()
    s = config["arch"]["sequence_length"]
    held = 3 * 2 * s * 6 / 16.0     # an even load: 8 of 128 held, 3 blocks
    monkeypatch.setattr(model_scopes, "counter", lambda run, name: held)
    need = (flops.train_flops_per_step(config, 2) + 6 * config["arch"][
        "expert_product_macs_per_assignment"] * held)
    if s == 8192:
        assert need == pytest.approx(28.7e12, rel=5e-3)
    least = need / 197e12
    for busy in (least, 3 * least):
        got = reader("sparse_mfu").read(doctored_run(trace={"arms": {
            "sparse": {"busy_s_per_step": busy}}}))
        assert got == pytest.approx(100.0 * least / busy) and got <= 100.0


STEP = "jit(sparse_step_fn)/fwd_bwd/"
BACK = STEP + "transpose(jvp(NemotronH))/fwd_bwd/jvp(NemotronH)/checkpoint/"
MIXER = "blocks_1/mixer/ssm/"


@pytest.mark.parametrize("tf_op,scope", [
    (STEP + "jvp(NemotronH)/" + MIXER + "ssm_scan/while/body/dot_general",
     "ssm_scan"),
    (BACK + "rematted_computation/" + MIXER + "ssm_conv/mul", "ssm_conv"),
    (BACK + MIXER + "ssm_norm_gate/logistic", "ssm_norm_gate"),
    (STEP + "jvp(NemotronH)/" + MIXER + "ssm_in_proj/in_proj/dot_general",
     "ssm_in_proj"),
    (STEP + "jvp(NemotronH)/" + MIXER + "ssm_out_proj/out_proj/dot_general",
     "ssm_out_proj"),
    (STEP + "jvp(NemotronH)/" + MIXER + "slice", "ssm"),
    (STEP + "jvp(NemotronH)/blocks_6/attn/attn_full/custom_vjp_call",
     "attn_full"),
    (STEP + "jvp(NemotronH)/blocks_6/attn/attn_proj/q_proj/dot_general",
     None),
    (STEP + "jvp(NemotronH)/blocks_2/moe/shared/moe_shared/dot_general",
     "moe_shared"),
    (STEP + "jvp(NemotronH)/blocks_2/moe/moe_router/top_k", "moe_router"),
    (STEP + "jvp(NemotronH)/blocks_2/moe/moe_experts/moe_gate/square",
     "moe_experts"),
    (STEP + "jvp(NemotronH)/lm_head/dot_general", "lm_head"),
    (STEP + "jvp(NemotronH)/blocks_2/norm/rms_norm/mul", None),
    (STEP + "jvp(NemotronH)/blocks_2/add", None)])
def test_the_innermost_model_scope_of_an_op_name(tf_op, scope):
    assert model_scopes.scope_of(
        tf_op, real_config()["model_scopes"]) == scope


def test_the_mixer_is_no_name_of_the_scope_trees():
    """`scope_tree.MODEL_NAMES` is older than the mixer and is not edited:
    an operation under `ssm_scan` reads as under no name of a model's, so
    `fwd_bwd_unnamed_ms` would hold `ssm_ms` here (as `lfm2_conv_dp1`'s
    holds `short_conv_ms`) and the cell is not on its list."""
    chain, which, _ = scope_tree.parse(
        BACK + "rematted_computation/" + MIXER + "ssm_scan/while/body/mul:")
    assert chain == ("fwd_bwd",) and which == "recomputed"
    chain, _, _ = scope_tree.parse(
        STEP + "jvp(NemotronH)/blocks_2/norm/rms_norm/mul:")
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
                    "(record_nemotronh_scope_trace.py)")
    for module in (model_scopes, scope_tree, trace_reduce):
        monkeypatch.setattr(module, "find_xplanes", lambda d: [path],
                            raising=False)
    return path


def test_the_new_readers_on_the_recorded_trace(only_the_recording):
    """A TPU's trace of the tiny model's sparse step: each of the mixer's
    scopes carries time in it, the parts add up to the whole, and the
    three shares are shares."""
    run = recorded_run()
    got = {name: reader(name).read(run) for name in NEW_METRICS}
    assert all(v is not None and v > 0 for v in got.values()), got
    own = model_scopes.scope_ms(run, "ssm") or 0.0
    assert got["ssm_ms"] == pytest.approx(
        own + got["ssm_proj_ms"] + got["ssm_conv_ms"] + got["ssm_scan_ms"]
        + got["ssm_norm_gate_ms"])
    for name in SHARES:
        assert 0 < got[name] < 100, name
    tokens = 3 * 2 * 32
    assert ssd_ops.scan_flops_per_step(run) == tokens * 4 * (
        2 * 2 * 16 * 16 * 4)
    # the accepted readers the cell joins find their scopes in it too
    for name in ("attn_full_ms", "attn_proj_ms", "moe_experts_ms",
                 "moe_router_ms", "moe_shared_ms", "lm_head_ms",
                 "rms_norm_ms", "fwd_recomputed_ms"):
        assert reader(name).read(run) > 0, name
    # the mixer is what `fwd_bwd_unnamed_ms` would mostly hold: the cell
    # is not on that reader's list
    assert reader("fwd_bwd_unnamed_ms").read(run) > got["ssm_ms"]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_finds_nothing_where_the_program_names_nothing(name):
    """An untraced run; and the recorded trace of a program from before
    the model (`testdata/tiny_sparse_4steps`): no scope of the mixer's:
    None, and nothing raises."""
    assert reader(name).read(doctored_run()) is None
    with open(os.path.join(TESTDATA, "tiny_sparse_4steps.block.json")) as f:
        block = json.load(f)
    block["traced"] = True
    r = doctored_run(blocks={"sparse": [block]},
                     trace_dirs={"sparse": [TESTDATA]},
                     trace={"arms": {"sparse": {"busy_s_per_step": 0.1}}})
    assert reader(name).read(r) is None
