"""The configuration `joyai_llm_flash` (PR 33) through the harness at a tiny
size on the CPU, from a throw-away root that this file writes (files and
entries only; the reference and the readers are the real ones, found by
name), its operation and byte counts against direct counts, and its new
readers on doctored runs and on the recorded trace of a program that names
none of the model's scopes."""

import copy
import json
import os

import pytest

from benchmarks import flops, harness, mla_ops, model_scopes, moe_ops
from test_harness_cpu import drive

CELL = "joyai_mla_dp1"
SCOPE_METRICS = ["attn_mla_ms", "mla_proj_ms", "moe_shared_ms",
                 "dense_mlp_ms"]
ROOFLINES = {"mla_fwd_roofline": "splash_mha_fwd_residuals",
             "mla_dq_roofline": "splash_mha_dq_no_residuals",
             "mla_dkv_roofline": "splash_mha_dkv_no_residuals"}
EXPERTS_ROOFLINE = "ragged_dot_roofline.moe_layers"
NEW_METRICS = SCOPE_METRICS + list(ROOFLINES) + [EXPERTS_ROOFLINE]
# the accepted readers that the cell joins beside those all four cells list
JOINED = ["moe_experts_ms", "moe_router_ms", "lm_head_ms",
          "moe_load_max_over_mean", "sparse_mfu", "ragged_dot_ms"]
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def real_config() -> dict:
    return harness.load_cell(CELL)["config_data"]


def reader(name):
    return harness.load_layer_metric(
        os.path.join(harness.HERE, "layer_metrics"), name)


def tiny_config() -> dict:
    """The real file with every size made tiny: what the reference reads
    (the published config's own keys) and what the trainer is given."""
    cfg = copy.deepcopy(real_config())
    positions, vocab, steps = 32, 50, 6
    cfg.update(
        name="tiny_joyai", hidden_size=64, num_attention_heads=4,
        q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
        moe_intermediate_size=32, n_routed_experts=4, num_experts_per_tok=2,
        num_hidden_layers=3, vocab_size=vocab,
        published={"n_routed_experts": 8},
        share={"expert_share": 1, "expert_shares": 2},
        examples_per_worker=steps,
        dataset_kwargs={"vocab_size": vocab, "bptt": positions},
        dataset_kwargs_per_worker={
            "synthetic_tokens_n": 2 * (positions * steps + 1)})
    cfg["trainer"].update(
        compute_dtype="float32", wire="off", density=0.01,
        model_kwargs={
            "hidden_size": 64, "num_layers": 3, "dense_width": 96,
            "num_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
            "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
            "num_experts": 8, "experts_per_token": 2, "expert_width": 32,
            "expert_share": 1, "expert_shares": 2,
            "num_nextn_predict_layers": 0, "seq_len": positions})
    cfg["states"].update(compute_dtype="float32", kernel_mode="interpret")
    cfg["matmul_layers"] = [{"name": "lm_head", "positions": positions,
                             "k": 64, "n": vocab}]
    cfg["arch"].update(expert_product_macs_per_assignment=3 * 64 * 32,
                       attention_layers=3, expert_layers=2,
                       sequence_length=positions)
    # float32 throughout: the sound runs read 1e-6 at most, the float8
    # control 1e-2 at least (tests/test_joyai_flash.py has both at this size)
    cfg["limits"].update(
        loss_gap_first=1e-4, loss_gap=1e-4, head_grad_rel_err=1e-3,
        grad_rel_err=1e-3, grad_norm_gap=1e-3, delta_norm_gap=1e-3,
        selected_over_k=[0.2, 200.0])
    return cfg


@pytest.fixture(scope="module")
def joyai_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny_joyai"))
    bdir = os.path.join(root, "benchmarks")
    for sub in ("configs", "traffic"):
        os.makedirs(os.path.join(bdir, sub))
    with open(os.path.join(bdir, "configs", "tiny_joyai.json"), "w") as f:
        json.dump(tiny_config(), f)
    mix = dict(harness.load_cell(CELL)["mix"], block_seconds=0.2)
    with open(os.path.join(bdir, "traffic", "dp1_sparse_blocks.json"),
              "w") as f:
        json.dump(mix, f)
    real = harness.load_benchmark()
    bench = {
        "command": real["command"], "paths": ["benchmarks"], "run_seconds": 1,
        "configs": [{"name": "tiny_joyai", "source": "throw-away",
                     "file": "benchmarks/configs/tiny_joyai.json",
                     "reduced": [], "why": "test"}],
        "workloads": [{"name": "tiny_mla", "config": "tiny_joyai",
                       "traffic": "dp1_sparse_blocks", "chips": 1,
                       "why": "test"}],
        "end_to_end": [m for m in real["end_to_end"]
                       if m["name"] != "dense_examples_per_s"],
        "per_layer": [dict(m, workloads=["tiny_mla"])
                      for m in real["per_layer"]
                      if CELL in m.get("workloads", ())]}
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_the_configuration_runs_end_to_end_on_the_cpu(joyai_root, capsys):
    rc, result, out = drive(joyai_root, capsys, "tiny_mla")
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
        assert m["source"] == "device_trace"
        assert m["unit"] == ("%" if "roofline" in name else "ms")
        assert hasattr(reader(name), "read")
    for name in JOINED:
        assert metrics[name]["workloads"] == ["mellum2_moe_dp1", CELL]
    listed = set(by_name(cell["per_layer"]))
    assert set(NEW_METRICS) | set(JOINED) <= listed
    # every metric all four older cells list, this cell lists too
    older = [w["name"] for w in bench["workloads"] if w["name"] != CELL]
    for m in bench["per_layer"]:
        if set(older) <= set(m.get("workloads", older)):
            assert m["name"] in listed, m["name"]
    # the readers that add up every Mosaic call of a step, the other
    # model's kernels and scopes, and the reader that divides by every layer
    assert not {"ef_select_ms", "ef_select_roofline", "dense_mfu",
                "exchange_ms", "attn_window_ms", "attn_full_ms",
                "splash_fwd_roofline", "splash_dq_roofline",
                "splash_dkv_roofline", "ragged_dot_roofline"} & listed
    assert sum(1 for w in bench["workloads"] if w["chips"] == 4) == 1
    config = cell["config_data"]
    assert config["arch"]["num_params"] == 413959168
    assert [config[k] for k in (
        "hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "moe_intermediate_size", "intermediate_size", "num_experts_per_tok",
        "routed_scaling_factor")] == [
        2048, 32, 1536, 512, 128, 64, 128, 768, 7168, 8, 2.5]
    entry = by_name(bench["configs"])[cell["config"]]
    assert entry["reduced"] == list(config["reduced"]) == [
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers", "nworkers"]
    assert set(config["model_scopes"]) >= {
        "attn_mla", "mla_proj", "moe_router", "moe_experts", "moe_shared",
        "dense_mlp", "lm_head"}


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
        "num_hidden_layers": 40, "n_routed_experts": 256,
        "vocab_size": 129280, "num_nextn_predict_layers": 1}
    assert config["published"]["n_routed_experts"] == 256


def test_pairs_operations_and_bytes_against_a_direct_count():
    for s in (1, 5, 32):
        assert mla_ops.pairs(s) == sum(1 for i in range(s)
                                       for j in range(s) if j <= i)
    config = real_config()
    s, heads, layers, seqs = 8192, 32, 5, 2
    pairs = mla_ops.pairs(s)
    assert pairs == config["arch"]["attention_pairs"]["causal"] == 33558528
    listed = {(l["name"].split(".")[-1], l["k"]): l["positions"]
              for l in config["matmul_layers"]
              if l["name"].split(".")[-1] in ("scores", "values")}
    assert listed == {("scores", 192): pairs, ("values", 128): pairs}
    # products at 192 and at 128 a pair and head, 2 operations an entry
    want = {"splash_mha_fwd_residuals": 192 + 128,
            "splash_mha_dq_no_residuals": 2 * 192 + 128,
            "splash_mha_dkv_no_residuals": 2 * 192 + 2 * 128}
    for kernel, entries in want.items():
        assert mla_ops.flops_per_pass(config, kernel, seqs) == (
            2 * entries * heads * pairs * layers * seqs)
    # the forward kernel is a third of the step's attention operations as
    # `flops.py` counts them from `matmul_layers` (forward and twice that)
    attention = sum(l["positions"] * l["k"] * l["n"]
                    for l in config["matmul_layers"]
                    if l["name"].split(".")[-1] in ("scores", "values"))
    assert 2 * attention * seqs == mla_ops.flops_per_pass(
        config, "splash_mha_fwd_residuals", seqs)
    # bytes: q k | v o | lse and so on, each once
    row192, row128, stat = 2 * 192, 2 * 128, 4 * 128
    want = {"splash_mha_fwd_residuals": 2 * row192 + 2 * row128 + stat,
            "splash_mha_dq_no_residuals": 3 * row192 + 2 * row128 + 2 * stat,
            "splash_mha_dkv_no_residuals": 3 * row192 + 3 * row128 + 2 * stat}
    for kernel, per_position in want.items():
        got = mla_ops.bytes_per_pass(config, kernel, seqs)
        assert got == per_position * heads * s * layers * seqs
        # bound by operations, far from the ridge
        assert got / 819e9 < 0.25 * mla_ops.flops_per_pass(
            config, kernel, seqs) / 197e12


def test_the_counted_operations_are_the_issues():
    """444 M multiply-adds a token forward, 77 % of them latent attention's,
    43.7 TFLOP a step."""
    config = real_config()
    tokens, held = 16384, 4 * 16384 * 8 / 32
    macs = (flops.forward_macs_per_example(config) * 2
            + config["arch"]["expert_product_macs_per_assignment"] * held)
    assert macs / tokens == pytest.approx(444.3e6, rel=1e-3)
    mla = 2 * sum(l["positions"] * l["k"] * l["n"]
                  for l in config["matmul_layers"]
                  if l["name"].split(".")[-1] in (
                      "q_a_proj", "q_b_proj", "kv_a_proj", "kv_b_proj",
                      "scores", "values", "o_proj"))
    assert mla / macs == pytest.approx(0.77, abs=0.005)
    assert 6 * macs == pytest.approx(43.7e12, rel=2e-3)


def doctored_run(**over):
    config = real_config()
    r = {"config": config, "cell": {"chips": 1}, "peaks": V5E,
         "mix": {"nworkers": 1}, "global_batch": {"sparse": 2},
         "blocks": {"sparse": []}, "trace": None}
    r.update(over)
    return r


@pytest.mark.parametrize("name", list(ROOFLINES))
def test_an_attention_kernels_share_is_at_most_100(monkeypatch, name):
    """At the least time its calls could take it reads 100, at any longer
    time less; ten calls a step are two passes over five layers."""
    config = real_config()
    kernel = ROOFLINES[name]
    least = mla_ops.flops_per_pass(config, kernel, 2) / 197e12
    for calls, seconds, want in ((5.0, least, 100.0), (10.0, 2 * least, 100.0),
                                 (5.0, 2 * least, 50.0),
                                 (10.0, 5 * least, 40.0)):
        monkeypatch.setattr(model_scopes, "kernel", lambda run, k: {
            "s_per_step": seconds, "calls_per_step": calls}
            if k == kernel else None)
        got = reader(name).read(doctored_run())
        assert got == pytest.approx(want) and got <= 100.0 + 1e-9
    # the other model's kernels are not this reader's
    monkeypatch.setattr(model_scopes, "kernel", lambda run, k: {
        "s_per_step": least, "calls_per_step": 5.0}
        if k == kernel.replace("mha", "mqa") else None)
    assert reader(name).read(doctored_run()) is None


def test_the_grouped_products_share_counts_the_layers_that_have_experts(
        monkeypatch):
    """24 calls a step over FOUR expert layers are 2 passes of 3 products
    (`moe_ops` would divide by the five layers); the weights are four
    layers'; at few rows the experts' bytes bound the kernel."""
    config = real_config()
    share = reader(EXPERTS_ROOFLINE)
    macs = config["arch"]["expert_product_macs_per_assignment"]
    weights = 2.0 * 4 * 8 * macs

    def least(held):
        rows = 2.0 * held * 3 * (2048 + 768)
        return max(2.0 * macs * held / 197e12, (rows + weights) / 819e9)

    for held, bound_by_bytes in ((4 * 4096.0, False), (4 * 1024.0, True)):
        assert (least(held) > 2.0 * macs * held / 197e12) == bound_by_bytes
        monkeypatch.setattr(model_scopes, "counter", lambda run, name: held)
        for seconds, want in ((2 * least(held), 100.0),
                              (8 * least(held), 25.0)):
            monkeypatch.setattr(model_scopes, "kernel", lambda run, name: {
                "s_per_step": seconds, "calls_per_step": 24.0}
                if name == moe_ops.KERNEL else None)
            got = share.read(doctored_run())
            assert got == pytest.approx(want) and got <= 100.0 + 1e-9
    # a configuration that does not say how many layers have experts, or a
    # program without the counter: nothing
    other = copy.deepcopy(config)
    del other["arch"]["expert_layers"]
    assert share.read(doctored_run(config=other)) is None
    monkeypatch.setattr(model_scopes, "counter", lambda run, name: None)
    assert share.read(doctored_run()) is None


def test_sparse_mfu_cannot_pass_100_in_this_cell(monkeypatch):
    config = real_config()
    held = 4 * 4096.0
    monkeypatch.setattr(model_scopes, "counter", lambda run, name: held)
    need = (flops.train_flops_per_step(config, 2) + 6 * config["arch"][
        "expert_product_macs_per_assignment"] * held)
    assert need == pytest.approx(43.7e12, rel=2e-3)
    least = need / 197e12
    for busy in (least, 3 * least):
        got = reader("sparse_mfu").read(doctored_run(trace={"arms": {
            "sparse": {"busy_s_per_step": busy}}}))
        assert got == pytest.approx(100.0 * least / busy) and got <= 100.0


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(step)/fwd_bwd/JoyAIFlash/layers_0/attn/attn_mla/dot", "attn_mla"),
    ("jit(step)/fwd_bwd/transpose(jvp(JoyAIFlash))/while/body/closed_call/"
     "checkpoint/expert_layers/attn/attn_mla/custom_vjp_call", "attn_mla"),
    ("jit(step)/fwd_bwd/jvp(JoyAIFlash)/while/body/closed_call/"
     "expert_layers/attn/mla_proj/q_b_proj/dot_general", "mla_proj"),
    ("jit(step)/fwd_bwd/transpose(jvp(JoyAIFlash))/while/body/closed_call/"
     "checkpoint/rematted_computation/expert_layers/moe/shared/moe_shared/"
     "dot_general", "moe_shared"),
    ("jit(step)/fwd_bwd/JoyAIFlash/layers_0/mlp/dense_mlp/dot_general",
     "dense_mlp"),
    ("jit(step)/fwd_bwd/jvp(JoyAIFlash)/while/body/closed_call/"
     "expert_layers/moe/moe_router/top_k", "moe_router"),
    ("jit(step)/fwd_bwd/JoyAIFlash/mtp/mtp_block/attn/attn_mla/dot",
     "attn_mla"),
    ("jit(step)/fwd_bwd/JoyAIFlash/mtp/mtp_proj/dot_general", "mtp"),
    ("jit(step)/fwd_bwd/jvp(JoyAIFlash)/while/body/closed_call/"
     "expert_layers/add", None)])
def test_the_innermost_model_scope_of_an_op_name(tf_op, scope):
    assert model_scopes.scope_of(
        tf_op, real_config()["model_scopes"]) == scope


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_finds_nothing_where_the_program_names_nothing(name):
    """An untraced run; and the recorded trace of a program from before
    the model (`testdata/tiny_sparse_4steps`): no scope of the model's, no
    kernel of its names, no counter: None, and nothing raises."""
    assert reader(name).read(doctored_run()) is None
    data = os.path.join(harness.HERE, "testdata")
    with open(os.path.join(data, "tiny_sparse_4steps.block.json")) as f:
        block = json.load(f)
    block["traced"] = True
    r = doctored_run(blocks={"sparse": [block]},
                     trace_dirs={"sparse": [data]},
                     trace={"arms": {"sparse": {"busy_s_per_step": 0.1}}})
    assert reader(name).read(r) is None
