"""`scope_tree.py` (PR 36): on `op_name`s worked out by eye, on made-up rows,
and on two small traces recorded on a TPU v5 lite chip by
`record_scope_trace.py` from the tiny `mellum2` and `joyai_flash`
configurations, whose steps carry every scope a model opens but
`joyai_flash`'s `layer_scan`, which is younger than its recording
(`testdata/tiny_*_scopes_4steps`): children and remainder add up to the
parent, the three passes to `fwd_bwd`, an operation without `op_name` lands
in no scope, and `span_reduce` and `model_scopes` read from the same file
what they read before. Then the new readers: numbers from those traces,
nothing from an untraced run or from a trace that names no scope."""

import json
import os
import shutil
from collections import namedtuple

import pytest

from benchmarks import harness, model_scopes, scope_tree as st
from benchmarks import span_reduce as sr
from gaussiank_sgd_tpu.telemetry import tracing

TESTDATA = os.path.join(harness.HERE, "testdata")
RECORDED = {"mellum2": "tiny_mellum2_scopes_4steps",
            "joyai": "tiny_joyai_scopes_4steps"}
CELLS = {"mellum2": "mellum2_moe_dp1", "joyai": "joyai_mla_dp1"}
TREE_METRICS = [
    "moe_to_rows_ms", "moe_to_tokens_ms", "moe_product_glue_ms", "moe_route_sort_ms", "attn_proj_ms", "mla_q_ms",
    "mla_kv_ms", "mla_out_ms", "mla_assemble_ms", "rms_norm_ms",
    "fwd_bwd_unnamed_ms", "fwd_recomputed_ms"]
SPAN_METRICS = ["flatten_ms", "ef_select_scope_ms", "no_scope_ms",
                "producer_assemble_ms"]
F, R, B = st.PASSES
STEP = "jit(sparse_step_fn)/fwd_bwd/"
BACK = STEP + "transpose(jvp(Mellum2))/fwd_bwd/jvp(Mellum2)/checkpoint/"


def reader(name):
    return harness.load_layer_metric(
        os.path.join(harness.HERE, "layer_metrics"), name)


# ------------------------------------------------------------- by the eye

@pytest.mark.parametrize("tf_op,chain,which,module", [
    (STEP + "jvp(Mellum2)/layers_0/attn/attn_proj/q_proj/dot_general:",
     ("fwd_bwd", "attn_proj"), F, "Mellum2/layers_0/attn/q_proj"),
    # the recomputed forward sits inside the transposition's path
    (BACK + "rematted_computation/layers_1/attn/attn_proj/rope/mul:",
     ("fwd_bwd", "attn_proj", "rope"), R, "Mellum2/layers_1/attn"),
    (BACK + "layers_1/moe/moe_experts/transpose(jvp(moe_to_rows))/"
     "tkh,tk->th/dot_general:",
     ("fwd_bwd", "moe_experts", "moe_to_rows"), B, "Mellum2/layers_1/moe"),
    # a name is on the chain once; a jitted library function is no module
    (BACK + "layers_1/moe/moe_experts/transpose(jvp(moe_product_glue))/"
     "moe_product_glue/jit(_where)/select_n:",
     ("fwd_bwd", "moe_experts", "moe_product_glue"), B,
     "Mellum2/layers_1/moe"),
    (STEP + "jvp(Mellum2)/embed/embed/gather:", ("fwd_bwd", "embed"), F,
     "Mellum2"),
    # a scanned body: the wrappers are stripped, the module path stays
    (STEP + "transpose(jvp(JoyAIFlash))/while/body/closed_call/checkpoint/"
     "rematted_computation/expert_layers/attn/mla_proj/mla_q/q_a_norm/"
     "rms_norm/mul:", ("fwd_bwd", "mla_proj", "mla_q", "rms_norm"), R,
     "JoyAIFlash/expert_layers/attn/q_a_norm"),
    (STEP + "jvp(JoyAIFlash)/mtp/mtp_block/moe/moe_router/moe_route_sort/"
     "jit(argsort)/sort:", ("fwd_bwd", "mtp", "moe_router", "moe_route_sort"),
     F, "JoyAIFlash/mtp_block/moe"),
    (STEP + "jvp(Mellum2)/layers_2/add:", ("fwd_bwd",), F,
     "Mellum2/layers_2"),
    (STEP + "jvp()/reduce_sum:", ("fwd_bwd",), F, ""),
    # inside fwd_bwd a module may be called what a phase is called
    (STEP + "jvp(VGG16)/update/conv_general_dilated:", ("fwd_bwd",), F,
     "VGG16"),
    # outside it the head is `span_reduce`'s word and nothing follows
    ("jit(sparse_step_fn)/ef_select/vmap(pack)/gather:", ("pack",), "", ""),
    ("jit(sparse_step_fn)/update/rms_norm/mul:", ("update",), "", ""),
    ("jit(sparse_step_fn)/jit(_threefry_fold_in)/slice:", (), "", ""),
    ("", (), "", ""),
])
def test_the_whole_path_of_an_op_name(tf_op, chain, which, module):
    assert st.parse(tf_op) == (chain, which, module)
    assert st.parse(tf_op)[0][:1] == tuple(filter(None, [sr.scope_of(tf_op)]))


ROWS = {(("fwd_bwd",), F): 1.0, (("fwd_bwd",), B): 2.0,
        (("fwd_bwd", "moe_experts"), F): 0.5,
        (("fwd_bwd", "moe_experts", "moe_gate"), R): 0.25,
        (("fwd_bwd", "moe_experts", "moe_to_rows"), B): 4.0,
        (("fwd_bwd", "mtp", "moe_experts", "moe_gate"), F): 8.0,
        (("fwd_bwd", "mla_proj", "mla_q", "rms_norm"), F): 16.0,
        (("fwd_bwd", "rms_norm"), B): 32.0,
        (("update",), ""): 64.0, ((), ""): 128.0}


def test_sums_over_made_up_rows():
    assert st.total(ROWS) == 255.75
    assert st.total(ROWS, ("fwd_bwd",)) == 63.75
    assert st.total(ROWS, ("fwd_bwd",), exact=True) == 3.0
    assert st.total(ROWS, ("fwd_bwd",), which=B) == 38.0
    assert st.total(ROWS, ("moe_experts",)) == 12.75     # the module's too
    assert st.total(ROWS, innermost="moe_gate") == 8.25
    assert st.total(ROWS, innermost="moe_experts") == 0.5
    assert st.total(ROWS, innermost="rms_norm") == 48.0
    assert st.total(ROWS, innermost="rms_norm", without=("mla_q",)) == 32.0
    assert st.total(ROWS, (), exact=True) == 128.0


def test_the_tree_line_shows_every_parent_with_its_remainder():
    line = st.tree_line({k: v / 1e3 for k, v in ROWS.items()})
    assert line == (
        "update 64.000, fwd_bwd 63.750 [rms_norm 32.000, mla_proj 16.000 "
        "[mla_q 16.000 [rms_norm 16.000, rest 0.000], rest 0.000], mtp 8.000 "
        "[moe_experts 8.000 [moe_gate 8.000, rest 0.000], rest 0.000], "
        "moe_experts 4.750 [moe_to_rows 4.000, moe_gate 0.250, rest 0.500], "
        "rest 3.000], no scope 128.000")


# ------------------------------------------------------ the recorded traces

@pytest.fixture(scope="module", params=sorted(RECORDED))
def recorded(request, tmp_path_factory):
    """(model, a trace directory that holds the recording as the profiler
    names it, its block, what `scope_tree` reads from it)."""
    name = RECORDED[request.param]
    tdir = str(tmp_path_factory.mktemp(name))
    shutil.copy(os.path.join(TESTDATA, name + ".xspace.pb"),
                os.path.join(tdir, "vm.xplane.pb"))
    with open(os.path.join(TESTDATA, name + ".block.json")) as f:
        block = json.load(f)
    read = st.reduce_device(st.find_xplanes(tdir), block["steps"])
    return request.param, tdir, block, read


def test_children_and_remainder_are_the_parent(recorded):
    model, _, _, read = recorded
    rows = read["rows"]
    names = {n for chain, _ in rows for n in chain}
    parents = {"mellum2": ("fwd_bwd", "moe_experts", "moe_router",
                           "attn_proj"),
               "joyai": ("fwd_bwd", "moe_experts", "moe_router", "mla_proj",
                         "mla_assemble", "mla_q", "mla_kv")}[model]
    assert set(parents) <= names
    for parent in parents:
        whole = st.total(rows, (parent,))
        inner = {chain[-1] for chain, _ in rows if parent in chain}
        assert len(inner) > 1, parent
        parts = sum(st.total(rows, (parent,), innermost=n) for n in inner)
        assert parts == pytest.approx(whole, rel=1e-9), parent
        assert 0 <= st.total(rows, (parent,), innermost=parent) < whole


def test_the_new_scopes_are_on_the_chips_operations(recorded):
    model, _, _, read = recorded
    by_name = {}
    for chain, which in read["rows"]:
        for n in chain[1:]:
            by_name.setdefault(n, set()).add(which)
    new = {"moe_to_rows", "moe_to_tokens", "moe_gate", "moe_product_glue",
           "moe_route_sort", "rope", "rms_norm", "embed", "loss"}
    new |= ({"attn_proj"} if model == "mellum2"
            else {"mla_q", "mla_kv", "mla_out", "mla_assemble"})
    assert new <= set(by_name)
    # XLA names a fusion after ONE of its constituents, so a small scope's
    # pass may read under its neighbour's name: the large ones have all
    for n in ("moe_to_rows", "rms_norm", "rope"):
        assert by_name[n] == {F, R, B}, n
    assert R not in by_name["loss"] | by_name["embed"]
    assert B not in by_name["moe_route_sort"]


def test_the_three_passes_are_fwd_bwd_and_span_reduce_reads_the_same(
        recorded):
    _, tdir, block, read = recorded
    rows = read["rows"]
    dev = sr.reduce_device(tdir, block["steps"])
    per = dev["scope_s_per_step"]
    assert sum(st.total(rows, ("fwd_bwd",), which=p) for p in st.PASSES) \
        == pytest.approx(per["fwd_bwd"], rel=1e-9)
    assert all(st.total(rows, ("fwd_bwd",), which=p) > 0 for p in st.PASSES)
    # phase by phase, and what lies under none of them
    heads = {chain[:1] for chain, _ in rows}
    assert heads == {(k,) if k else () for k in per}
    for k, seconds in per.items():
        mine = st.total(rows, (k,)) if k else st.total(rows, (), exact=True)
        assert mine == pytest.approx(seconds, rel=1e-9), k
    assert st.total(rows) == pytest.approx(sum(per.values()), rel=1e-9)


def test_an_operation_without_op_name_lands_in_no_scope(recorded):
    """XLA's copies have no `op_name` at all; the grouped products' kernels
    have their own name for one and no path. Both are under no scope and
    in no pass, and the kernels are listed beside the tree."""
    _, tdir, block, read = recorded
    bare = 0.0
    planes = [p for p in sr.read_xspace(st.find_xplanes(tdir)[0])
              if p["lines"].get(sr.OPS_LINE)]
    for p in planes:
        for (_, _, _, tf_op), ps in sr.self_times(p["lines"][sr.OPS_LINE]):
            bare += ps if not tf_op else 0.0
    bare /= 1e12 * len(planes) * block["steps"]
    kernels = read["pathless_kernels"]
    assert st.parse("ragged-dot-none:") == ((), "", "")
    assert bare > 0 and kernels["ragged-dot-none"] > 0
    assert bare + sum(kernels.values()) <= st.total(
        read["rows"], (), exact=True) * (1 + 1e-9)
    assert {which for chain, which in read["rows"] if not chain} == {""}


def test_model_scopes_reads_what_it_read(recorded):
    """The innermost name of the CONFIGURATION's list is the older scope:
    from the tree's rows the same seconds."""
    model, tdir, block, read = recorded
    config = harness.load_cell(CELLS[model])["config_data"]
    scopes = config["model_scopes"]
    old = model_scopes.reduce_device(tdir, block["steps"], scopes,
                                     config["kernels_without_scope"])
    mine = {}
    for (chain, _), s in read["rows"].items():
        hit = [n for n in chain if n in scopes]
        if hit:
            mine[hit[-1]] = mine.get(hit[-1], 0.0) + s
    # a path that lost its head (the tiny `joyai` recording has one,
    # `checkpoint/expert_layers/attn/mla_proj/mla_assemble/add:`) names no
    # phase: `span_reduce` and the tree put it under no scope, `model_scopes`
    # looks no further than the model's name
    planes = [p for p in sr.read_xspace(st.find_xplanes(tdir)[0])
              if p["lines"].get(sr.OPS_LINE)]
    for p in planes:
        for (_, _, _, tf_op), ps in sr.self_times(p["lines"][sr.OPS_LINE]):
            scope = model_scopes.scope_of(tf_op, scopes)
            if scope and not st.parse(tf_op)[0]:
                mine[scope] += ps / 1e12 / len(planes) / block["steps"]
    for kernel, scope in config["kernels_without_scope"].items():
        if kernel in read["pathless_kernels"]:
            mine[scope] = mine.get(scope, 0.0) + read["pathless_kernels"][
                kernel]
    assert set(mine) == set(old["scope_s_per_step"]) and len(mine) >= 5
    for k, seconds in old["scope_s_per_step"].items():
        assert mine[k] == pytest.approx(seconds, rel=1e-9), k


def test_module_paths_need_no_scope(recorded):
    model, _, _, read = recorded
    top = {"mellum2": "Mellum2", "joyai": "JoyAIFlash"}[model]
    paths = set(read["modules"])
    assert all(p.split("/")[0] == top for p in paths)
    want = ({"Mellum2/layers_0/attn/q_proj", "Mellum2/norm"}
            if model == "mellum2" else
            {"JoyAIFlash/expert_layers/attn/q_b_proj",
             "JoyAIFlash/layers_0/mlp"})
    assert want <= paths
    assert not any(n in p.split("/") for p in paths for n in st.NAMES
                   if n not in ("embed",))      # a module of that name


# ------------------------------------------------------------- the readers

def doctored_run(**over):
    r = {"config": {}, "cell": {"chips": 1}, "mix": {"nworkers": 1},
         "blocks": {"sparse": []}, "trace": None}
    r.update(over)
    return r


def traced_run(tdir, block):
    return doctored_run(
        blocks={"sparse": [dict(block, traced=True)]},
        trace_dirs={"sparse": [tdir]},
        trace={"arms": {"sparse": {"busy_s_per_step": 0.1}}})


def test_the_readers_sums_close_on_a_recorded_trace(recorded, capsys):
    model, tdir, block, read = recorded
    run = traced_run(tdir, block)
    m = {n: reader(n).read(run) for n in TREE_METRICS}
    out = capsys.readouterr().out
    # one decode for all of them, its two lines printed once
    assert out.count("scope tree sparse, ms per step") == 1
    assert out.count("module paths sparse, ms per step") == 1
    block = {"mellum2": "Mellum2/layers_3 ", "joyai": "JoyAIFlash/layers_0 "}
    assert block[model] in out.split("module paths sparse")[1]
    assert "kernels whose op_name is no path" in out
    assert st.reduced(run) is run["scope_tree"]
    only = ({"attn_proj_ms"} if model == "mellum2" else
            {"mla_q_ms", "mla_kv_ms", "mla_out_ms", "mla_assemble_ms"})
    other = ({"attn_proj_ms", "mla_q_ms", "mla_kv_ms", "mla_out_ms",
              "mla_assemble_ms"} - only)
    assert all(m[n] is None for n in other)
    assert all(v > 0 for n, v in m.items() if n not in other)
    rows = read["rows"]
    experts = 1e3 * st.total(rows, ("moe_experts",))
    rest = 1e3 * st.total(rows, ("moe_experts",), innermost="moe_experts")
    gate = 1e3 * st.total(rows, innermost="moe_gate")    # printed, no metric
    assert (m["moe_to_rows_ms"] + m["moe_to_tokens_ms"] + gate
            + m["moe_product_glue_ms"] + rest) == pytest.approx(experts)
    if model == "joyai":
        whole = 1e3 * st.total(rows, ("mla_proj",))
        rest = 1e3 * st.total(rows, ("mla_proj",), innermost="mla_proj")
        assert (m["mla_q_ms"] + m["mla_kv_ms"] + m["mla_out_ms"]
                + m["mla_assemble_ms"] + rest) == pytest.approx(whole)
        inside = 1e3 * st.total(rows, innermost="rms_norm") - m["rms_norm_ms"]
        assert inside > 0           # the two norms inside the bottlenecks
        # this recording is older than the scope `layer_scan`: the scanned
        # body's own slicing and stacking is what has no name in it
        assert any(k.startswith("while/body/") for k in read["unnamed"])
    assert m["fwd_bwd_unnamed_ms"] < 0.5 * 1e3 * st.total(rows, ("fwd_bwd",))
    assert m["fwd_recomputed_ms"] == pytest.approx(
        1e3 * st.total(rows, ("fwd_bwd",), which=R))


@pytest.mark.parametrize("name", TREE_METRICS + SPAN_METRICS)
def test_a_reader_finds_nothing_where_there_is_nothing_to_read(name):
    """An untraced run; and the recorded trace of a program from before
    any scope (`testdata/tiny_sparse_4steps`): None, and nothing raises."""
    assert reader(name).read(doctored_run()) is None
    with open(os.path.join(TESTDATA, "tiny_sparse_4steps.block.json")) as f:
        block = json.load(f)
    assert reader(name).read(traced_run(TESTDATA, block)) is None


@pytest.mark.parametrize("name", ["attn_proj_ms", "mla_q_ms",
                                  "moe_to_rows_ms", "rms_norm_ms"])
def test_another_models_trace_names_no_such_scope(name):
    """The tiny VGG's recording carries the step's phases and no name of a
    transformer's."""
    with open(os.path.join(TESTDATA, "tiny_spans_4steps.block.json")) as f:
        block = json.load(f)
    run = traced_run(TESTDATA, block)
    run["scope_tree"] = st.reduce_device(       # no `.xplane.pb` to find
        [os.path.join(TESTDATA, "tiny_spans_4steps.xspace.pb")],
        block["steps"])
    assert reader(name).read(run) is None
    assert st.unnamed_ms(run) == pytest.approx(st.under_ms(run, "fwd_bwd"))


Rec = namedtuple("Rec", "spans")


def test_the_producers_time_is_the_median_over_the_counted_blocks(
        monkeypatch):
    def wait(i, t0, **fields):
        return tracing.Span("data_wait", f"w{i}", f"i{i}", t0, t0 + 10,
                            "host", fields)
    spans = [wait(0, 100, ready=2, assemble_ms=30.0, fresh=4),
             wait(1, 200, ready=2, assemble_ms=18.0, fresh=4),
             wait(2, 300, ready=2, fresh=4),               # no batch pulled
             wait(3, 400, ready=2, assemble_ms=20.0, fresh=4),
             wait(4, 2000, ready=2, assemble_ms=99.0, fresh=4)]   # outside
    monkeypatch.setattr(sr, "spans_of", lambda arm: Rec(spans))
    run = doctored_run(blocks={"sparse": [{"t0": 0.0, "t1": 1e-6}]})
    assert reader("producer_assemble_ms").read(run) == 20.0
    monkeypatch.setattr(sr, "spans_of", lambda arm: Rec(spans[2:3]))
    assert reader("producer_assemble_ms").read(run) is None


def test_the_benchmark_lists_the_new_readers_last_and_each_has_its_file():
    bench = harness.load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    cells = {w["name"] for w in bench["workloads"]}
    new = [n for n in TREE_METRICS + SPAN_METRICS if n in names]
    assert set(new) >= {"attn_proj_ms", "mla_q_ms", "moe_to_tokens_ms",
                        "fwd_bwd_unnamed_ms", "fwd_recomputed_ms",
                        "no_scope_ms", "flatten_ms", "ef_select_scope_ms",
                        "producer_assemble_ms"}
    first = min(names.index(n) for n in new)
    assert set(names[first:]) == set(new)       # appended, nothing between
    for m in bench["per_layer"][first:]:
        assert m["moves"] == "examples_per_s" and m["unit"] == "ms"
        assert set(m["workloads"]) <= cells
        assert os.path.exists(os.path.join(
            harness.HERE, "layer_metrics", m["name"] + ".py"))
    by = {m["name"]: m["workloads"] for m in bench["per_layer"]}
    assert by["no_scope_ms"] == [w["name"] for w in bench["workloads"]]
    assert by["attn_proj_ms"] == ["mellum2_moe_dp1"]
    assert by["mla_q_ms"] == ["joyai_mla_dp1"]
    assert by["producer_assemble_ms"] == ["vgg16_dp1", "vgg16_dp4"]
