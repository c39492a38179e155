"""The device scopes that `models/mellum2.py`, `models/joyai_flash.py` and the
`lm` loss open (PR 36), read from the COMPILED sparse step of each model at
tiny widths on the CPU: every name is on the operations of the passes it has
work in, the older names stay the outer ones (a per-layer metric reads the
innermost name of its configuration's list, so a new scope goes INSIDE the
one a metric reads), and little of `fwd_bwd` is left without a model's name.
Counts of instructions: what a trace of the program can name, never a time.
`benchmarks/scope_tree.parse` is the reader the benchmark uses."""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import Mesh

from benchmarks import model_scopes, scope_tree
from gaussiank_sgd_tpu.compressors import get_compressor
from gaussiank_sgd_tpu.models import get_model
from gaussiank_sgd_tpu.parallel.bucketing import plan_for_params
from gaussiank_sgd_tpu.parallel.flat_opt import FlatSGDM
from gaussiank_sgd_tpu.parallel.trainstep import build_dp_train_step
from gaussiank_sgd_tpu.training.losses import make_loss_fn

POSITIONS = 32
MODELS = {
    "mellum2": dict(
        hidden_size=64, num_layers=4, num_heads=4, num_kv_heads=2,
        head_dim=16, sliding_window=8, num_experts=8, experts_per_token=2,
        expert_width=32, expert_share=0, expert_shares=2,
        yarn_original_max=16),
    # with the prediction module, so its three norms and second loss count
    "joyai_flash": dict(
        hidden_size=64, num_layers=3, first_k_dense_replace=1,
        dense_width=96, num_heads=4, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        num_experts=8, experts_per_token=2, expert_width=32, expert_share=0,
        expert_shares=2, num_nextn_predict_layers=1),
    # its own scopes are the configuration's to name, not `scope_tree`'s:
    # `tests/test_lfm2_moe.py` reads this one's compiled step
    "lfm2_moe": dict(
        hidden_size=64, num_layers=4,
        layer_types=("conv", "full_attention", "conv", "conv"),
        num_dense_layers=1, dense_width=96, num_heads=4, num_kv_heads=2,
        head_dim=16, num_experts=8, experts_per_token=2, expert_width=32,
        expert_share=0, expert_shares=2),
    # window layers under rotary positions, a full layer under none, a
    # gate on every layer's attention output, four norms a layer
    "afmoe": dict(
        hidden_size=64, num_layers=4,
        layer_types=("sliding_attention", "sliding_attention",
                     "full_attention", "sliding_attention"),
        num_dense_layers=1, dense_width=96, num_heads=4, num_kv_heads=2,
        head_dim=16, sliding_window=8, num_experts=8, experts_per_token=2,
        expert_width=32, expert_share=0, expert_shares=2),
    # its mixer's scopes are the configuration's to name too:
    # `tests/test_qwen3_next.py` reads this one's compiled step
    "qwen3_next": dict(
        hidden_size=64, num_layers=4, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=16,
        linear_value_head_dim=16, num_heads=4, num_kv_heads=2, head_dim=32,
        num_experts=8, experts_per_token=2, expert_width=32,
        shared_expert_width=32, expert_share=0, expert_shares=2),
    # blocks of one module; the state-space mixer's scopes are the
    # configuration's to name: `tests/test_nemotron_h.py` reads this one's
    # compiled step
    "nemotron_h": dict(
        hidden_size=64, pattern="EMEM*", mamba_num_heads=4, mamba_head_dim=16,
        ssm_state_size=16, n_groups=2, chunk_size=8, num_heads=4,
        num_kv_heads=2, head_dim=16, num_experts=8, experts_per_token=2,
        expert_width=32, shared_expert_width=48, expert_share=0,
        expert_shares=2)}

F, R, B = scope_tree.PASSES
ALL = (F, R, B)
# the passes each new scope has operations in. Outside the recomputed layers:
# `embed`, `loss`. `moe_route_sort` sorts integers, which have no cotangent.
# `moe_to_tokens`' recomputed forward is dead code: the backward pass keeps
# its arguments, not its sum.
SHARED = {"moe_to_rows": ALL, "moe_to_tokens": (F, B), "moe_gate": ALL,
          "moe_product_glue": ALL, "moe_route_sort": (F, R), "rope": ALL,
          "rms_norm": ALL, "embed": (F, B), "loss": (F, B)}
PASSES_OF = {
    "mellum2": dict(SHARED, attn_proj=ALL),
    "joyai_flash": dict(SHARED, mla_q=ALL, mla_kv=ALL, mla_out=ALL,
                        mla_assemble=ALL, layer_scan=ALL),
    # the norm a branch leaves through needs the branch's output again in
    # the backward pass: here `moe_to_tokens`' recomputed sum is no dead code
    "afmoe": dict(SHARED, attn_proj=ALL, moe_to_tokens=ALL),
    # no positions: its attention block opens no `rope`; the state-space
    # mixer's five scopes are its configuration's to name
    # (`tests/test_nemotron_h.py`)
    "nemotron_h": dict({k: v for k, v in SHARED.items() if k != "rope"},
                       attn_proj=ALL)}
# new scope: the older scope that has to enclose it wherever it appears
ENCLOSED_BY = {"moe_to_rows": "moe_experts", "moe_to_tokens": "moe_experts",
               "moe_gate": "moe_experts", "moe_product_glue": "moe_experts",
               "moe_route_sort": "moe_router", "mla_q": "mla_proj",
               "mla_kv": "mla_proj", "mla_out": "mla_proj",
               "mla_assemble": "mla_proj"}
# the lists `benchmarks/configs/*.json` give `model_scopes.scope_of`
OLD_SCOPES = {
    "mellum2": ("attn_window", "attn_full", "moe_router", "moe_experts",
                "lm_head"),
    "joyai_flash": ("attn_mla", "mla_proj", "moe_router", "moe_experts",
                    "moe_shared", "dense_mlp", "lm_head"),
    "afmoe": ("attn_window", "attn_full", "attn_gate", "moe_router",
              "moe_experts", "moe_shared", "dense_mlp", "lm_head"),
    "nemotron_h": ("ssm", "ssm_in_proj", "ssm_conv", "ssm_scan",
                   "ssm_norm_gate", "ssm_out_proj", "attn_full",
                   "moe_router", "moe_experts", "moe_shared", "lm_head")}
# share of the instructions under `fwd_bwd` whose path holds no name of a
# model's: the residual additions, the counters, `jax.checkpoint`'s own
# barriers (3.4 % and 0.7 % at these sizes; 31 % and 5 % of the time on the
# chip before PR 36, PERF.md section 6)
UNNAMED_SHARE = {"mellum2": 0.06, "joyai_flash": 0.03, "afmoe": 0.06}


@contextlib.contextmanager
def no_compile_cache():
    """The persistent cache's key leaves out locations
    (`jax_compilation_cache_include_metadata_in_key` is off), and an
    operation's `op_name` is one: a cached executable answers with the names
    it was compiled under. So these programs are compiled anew."""
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


def compiled_op_names(model: str, **changed):
    spec = get_model(model, "ptb", vocab_size=50, dtype=jnp.float32,
                     seq_len=POSITIONS, **{**MODELS[model], **changed})
    tokens = jax.ShapeDtypeStruct((2, POSITIONS), jnp.int32)
    params = jax.eval_shape(
        lambda t: spec.module.init({"params": jax.random.PRNGKey(0)}, t,
                                   train=False), tokens)["params"]
    ts = build_dp_train_step(
        make_loss_fn(spec), None, get_compressor("auto", density=0.01),
        plan_for_params(params, 0.01),
        Mesh(np.array(jax.devices()[:1]), ("dp",)),
        flat_opt=FlatSGDM(lr=0.1, momentum=0.9, weight_decay=1e-4))
    state = jax.eval_shape(
        lambda p: ts.init_state(p, jax.random.PRNGKey(2), model_state={},
                                carry=()), params)
    with no_compile_cache():
        hlo = ts.sparse_step.lower(state, (tokens, tokens)).compile(
            ).as_text()
    return re.findall(r'op_name="([^"]*)"', hlo)


@pytest.fixture(scope="module")
def parsed():
    """{model: [(op_name, chain, pass)]} of each compiled sparse step."""
    cache = {}

    def of(model):
        if model not in cache:
            cache[model] = [(n, *scope_tree.parse(n)[:2])
                            for n in compiled_op_names(model)]
        return cache[model]
    return of


@pytest.mark.parametrize("model,scope", [
    (m, s) for m in sorted(PASSES_OF) for s in sorted(PASSES_OF[m])])
def test_a_new_scope_is_on_the_operations_of_its_passes(parsed, model, scope):
    seen = {which for _, chain, which in parsed(model) if scope in chain}
    assert seen == set(PASSES_OF[model][scope])
    # and the path says so in JAX's own words
    mine = [n for n, chain, _ in parsed(model) if scope in chain]
    again = [n for n in mine if "rematted_computation" in n]
    assert bool(again) == (R in seen)
    assert any("transpose(" in n for n in set(mine) - set(again)) == (
        B in seen)


@pytest.mark.parametrize("model,scope", [
    (m, s) for m in sorted(PASSES_OF) for s in sorted(PASSES_OF[m])
    if s in ENCLOSED_BY])
def test_the_older_scope_stays_the_outer_one(parsed, model, scope):
    """So `model_scopes.scope_of`, which takes the innermost name of the
    configuration's list, reads what it read."""
    outer = ENCLOSED_BY[scope]
    mine = [(n, chain) for n, chain, _ in parsed(model) if scope in chain]
    assert mine
    for name, chain in mine:
        assert chain.index(outer) < chain.index(scope), name
        assert model_scopes.scope_of(name, OLD_SCOPES[model]) == outer, name


@pytest.mark.parametrize("model", sorted(
    m for m in PASSES_OF if "rope" in PASSES_OF[m]))
def test_the_rotary_turn_is_inside_the_projections(parsed, model):
    outer = "mla_assemble" if model == "joyai_flash" else "attn_proj"
    chains = {chain for _, chain, _ in parsed(model) if "rope" in chain}
    assert chains and all(outer in c[:c.index("rope")] for c in chains)


@pytest.mark.parametrize("model", sorted(PASSES_OF))
def test_attention_proper_is_not_under_the_projections(parsed, model):
    proper = {"attn_window", "attn_full", "attn_mla"}
    chains = {chain for _, chain, _ in parsed(model) if proper & set(chain)}
    assert chains
    assert not any({"attn_proj", "mla_proj"} & set(c) for c in chains)


def test_a_norm_inside_latent_attention_counts_with_its_projection(parsed):
    chains = {c for _, c, _ in parsed("joyai_flash") if "rms_norm" in c}
    assert {c[-2] for c in chains} >= {"mla_q", "mla_kv", "fwd_bwd", "mtp"}


@pytest.mark.parametrize("model", sorted(UNNAMED_SHARE))
def test_little_of_fwd_bwd_has_no_name_of_the_models(parsed, model):
    under = [chain for _, chain, _ in parsed(model)
             if chain[:1] == ("fwd_bwd",)]
    unnamed = sum(1 for c in under if c == ("fwd_bwd",))
    assert 0 < unnamed < UNNAMED_SHARE[model] * len(under)
    # the three passes are all of it
    assert {w for _, c, w in parsed(model) if c[:1] == ("fwd_bwd",)} == {
        F, R, B}


def test_the_gate_on_the_attentions_output_is_inside_the_projections(parsed):
    """`attn_gate` (`attention.gated_output`) lies inside `attn_proj` on
    forward, recomputed and backward operations of every layer: the
    configuration lists it, so `model_scopes.scope_of` reads it as its own
    (`attn_gate_ms`), and `scope_tree`, which does not know the name, reads
    it with the scope around it (`attn_proj_ms`; `fwd_bwd_unnamed_ms` stays
    what it was)."""
    mine = [(n, chain, which) for n, chain, which in parsed("afmoe")
            if "/attn_gate/" in n]
    assert {which for _, _, which in mine} == {F, R, B}
    for i in range(4):
        assert {which for n, _, which in mine
                if f"/layers_{i}/" in n} == {F, R, B}, i
    for name, chain, _ in mine:
        parts = name.split("/")
        assert parts.index("attn_proj") < parts.index("attn_gate"), name
        assert chain == ("fwd_bwd", "attn_proj"), name
        assert model_scopes.scope_of(name, OLD_SCOPES["afmoe"]) == (
            "attn_gate"), name
    # the sigmoid and both multiplies, forward and backward
    for which, piece in ((F, "exp"), (F, "mul"), (R, "mul"), (B, "mul")):
        assert any(n.rsplit("/", 1)[-1].startswith(piece)
                   for n, _, w in mine if w == which), (which, piece)


@pytest.fixture(scope="module")
def cut_room():
    """`mellum2`'s step with 2 of its 8 experts held: room for 64 sorted
    rows of the 128 assignments, so the sum back to tokens goes over the
    rows that are there (`experts._summed`) on the small side of the layer's
    `cond` and over a row for every assignment on the other."""
    return [(n, *scope_tree.parse(n)[:2])
            for n in compiled_op_names("mellum2", expert_shares=4)]


@pytest.mark.parametrize("scope", ["moe_to_rows", "moe_to_tokens"])
def test_the_sum_over_the_rows_that_are_there_is_under_the_same_scopes(
        cut_room, scope):
    """Its slots' comparison and its product are `moe_to_tokens`' forward
    and `moe_to_rows`' backward, inside `moe_experts`: the two metrics read
    what they read."""
    mine = [(n, chain, which) for n, chain, which in cut_room
            if scope in chain]
    assert {which for _, _, which in mine} == set(SHARED[scope])
    for name, chain, _ in mine:
        assert chain.index("moe_experts") < chain.index(scope), name
        assert model_scopes.scope_of(name, OLD_SCOPES["mellum2"]) == (
            "moe_experts"), name
    # the new sum is what was compiled, in the pass it belongs to
    its_pass = F if scope == "moe_to_tokens" else B
    for piece in ("eq", "dot_general"):
        assert any(n.rsplit("/", 1)[-1].startswith(piece)
                   for n, _, which in mine if which == its_pass), piece
    # and nothing of it is `moe_experts`' alone
    loose = [n for n, chain, _ in cut_room
             if n.rsplit("/", 1)[-1].startswith(("eq", "dot_general"))
             and chain[-1:] == ("moe_experts",)]
    assert not loose, loose
