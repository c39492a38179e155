"""`models/afmoe.py` at tiny widths on the CPU (hidden 64, 4 query and 2
key/value heads of 16 with normed heads and a gated output, a window of 8
keys, 8 experts of 32 top-2 behind a sigmoid router with a selection bias
and a shared expert, a leading dense layer of 96, 32 positions, four layers
`sliding, sliding, full, sliding` cut from the published pattern, an untied
head), against the benchmark's plain reference
(`benchmarks/reference/trinity_mini.py`, which imports nothing of the
program) and against direct formulas."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import trinity_mini as ref
from gaussiank_sgd_tpu import models
from gaussiank_sgd_tpu.models import afmoe, get_model
from gaussiank_sgd_tpu.models.blocks import attention, common, rope
from gaussiank_sgd_tpu.models.blocks import experts as moe
from gaussiank_sgd_tpu.training.losses import make_loss_fn
from test_joyai_flash import as_tree, by_path, shapes_of

VOCAB, POSITIONS, WINDOW = 50, 32, 8
SLIDING, FULL = "sliding_attention", "full_attention"
# the pattern the tiny model is cut from, and which of its layers are held
PATTERN = [SLIDING, SLIDING, SLIDING, FULL, SLIDING, SLIDING]
HELD = [0, 2, 3, 4]
CONFIG = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                      "configs", "trinity_mini.json")


def tiny(share=0, shares=2, dtype=jnp.float32, experts=8, top=2, held=HELD,
         dense_layers=1):
    """(the program's model, the reference's configuration) of one share."""
    spec = get_model(
        "afmoe", "ptb", vocab_size=VOCAB, dtype=dtype, hidden_size=64,
        num_layers=len(held), layer_types=[PATTERN[i] for i in held],
        num_dense_layers=dense_layers, dense_width=96, num_heads=4,
        num_kv_heads=2, head_dim=16, sliding_window=WINDOW,
        num_experts=experts, experts_per_token=top, expert_width=32,
        expert_share=share, expert_shares=shares)
    cfg = {"hidden_size": 64, "num_hidden_layers": len(held),
           "layer_types": PATTERN, "num_dense_layers": dense_layers,
           "intermediate_size": 96, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 16,
           "sliding_window": WINDOW, "rope_theta": 10000,
           "rope_scaling": None, "rms_norm_eps": 1e-5,
           "num_experts": experts // shares, "num_experts_per_tok": top,
           "moe_intermediate_size": 32, "num_shared_experts": 1,
           "route_norm": True, "route_scale": 2.826, "score_func": "sigmoid",
           "mup_enabled": True, "tie_word_embeddings": False,
           "vocab_size": VOCAB, "published": {"num_experts": experts},
           "share": {"expert_share": share, "expert_shares": shares,
                     "layers": held}}
    return spec, cfg


def seeded(cfg, key=7):
    """The reference's seeded weights with a NON-ZERO selection bias, of
    the size of the scores' spread: it changes which experts are chosen,
    and does not choose alone."""
    weights = ref.init_params(jax.random.PRNGKey(key), cfg)
    for i, p in enumerate(sorted(weights)):
        if p.endswith("router_bias"):
            weights[p] = 0.03 * jax.random.normal(
                jax.random.PRNGKey(100 + i), weights[p].shape)
    return weights


def layer_weights(cfg, index, key=7):
    return {p[len(f"layers_{index}/"):]: v for p, v in seeded(cfg, key).items()
            if p.startswith(f"layers_{index}/")}


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(3)
    toks = rng.integers(0, VOCAB, (2, POSITIONS + 1)).astype(np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


def test_parameter_paths_are_the_references():
    spec, cfg = tiny()
    assert "afmoe" in models.NAMES and "afmoe" in models.TOKEN_MODELS
    assert spec.task == "lm" and spec.counters and spec.mtp_lambda == 0.0
    mine = shapes_of(spec)
    assert mine == {p: tuple(s) for p, s in ref.param_shapes(cfg).items()}
    # an untied head; four norms a layer; the gate's projection beside the
    # other four and the two head norms; dense first, then experts with a
    # shared one
    assert mine["lm_head"] == (64, VOCAB)
    assert mine["embed/embedding"] == (VOCAB, 64)
    for i in range(4):
        assert {p.split("/")[1] for p in mine
                if p.startswith(f"layers_{i}/") and p.endswith("/scale")} == {
            "input_norm", "post_attn_norm", "pre_mlp_norm", "post_mlp_norm"}
        assert mine[f"layers_{i}/attn/gate_proj/kernel"] == (64, 4, 16)
        assert mine[f"layers_{i}/attn/q_layernorm"] == (16,)
    assert "layers_0/mlp/w1" in mine and "layers_1/moe/shared/w1" in mine
    assert mine["layers_1/moe/router"] == (64, 8)
    assert mine["layers_1/moe/w1"] == (4, 64, 32)


def test_published_widths_give_both_parameter_counts():
    """The benchmark's cut (layers 0 and 2-5 of 32, 8 of 128 experts, 25 024
    rows of 200 192) at the published widths, from shapes alone; and the
    whole published model, 26.12 B with the gate's projection (25.86 B
    without it: the count does not decide the gate, the file's `assumed`
    does)."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    assert cfg["arch"]["num_params"] == 504147712
    assert sum(math.prod(s) for s in ref.param_shapes(cfg).values()) \
        == 504147712
    kw = {k: v for k, v in cfg["trainer"]["model_kwargs"].items()
          if k != "seq_len"}
    spec = get_model("afmoe", "ptb", vocab_size=cfg["vocab_size"], **kw)
    assert shapes_of(spec, 128) == {
        p: tuple(s) for p, s in ref.param_shapes(cfg).items()}
    assert list(spec.module.layer_types) == [
        cfg["layer_types"][i] for i in cfg["share"]["layers"]] == [
        SLIDING, SLIDING, FULL, SLIDING, SLIDING]
    # every width is the published one
    m = spec.module
    assert (m.hidden_size, m.num_heads, m.num_kv_heads, m.head_dim,
            m.expert_width, m.dense_width, m.num_experts,
            m.experts_per_token, m.num_shared_experts, m.sliding_window,
            m.rope_theta, m.rms_norm_eps, m.route_scale, m.mup_enabled) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"],
        cfg["moe_intermediate_size"], cfg["intermediate_size"],
        cfg["published"]["num_experts"], cfg["num_experts_per_tok"],
        cfg["num_shared_experts"], cfg["sliding_window"], cfg["rope_theta"],
        cfg["rms_norm_eps"], cfg["route_scale"], cfg["mup_enabled"]) == (
        2048, 32, 4, 128, 1024, 6144, 128, 8, 1, 2048, 10000, 1e-5, 2.826,
        True)
    # the whole model: the program's defaults are the published config
    whole = dict(cfg, **cfg["published"])
    whole["share"] = {"expert_share": 0, "expert_shares": 1,
                      "layers": list(range(32))}
    shapes = ref.param_shapes(whole)
    count = sum(math.prod(s) for s in shapes.values())
    assert count == 26123974400
    gates = sum(math.prod(s) for p, s in shapes.items() if "gate_proj" in p)
    assert gates == 32 * 8388608 and count - gates == 25855538944
    assert shapes_of(get_model("afmoe", "ptb"), 128) == {
        p: tuple(s) for p, s in shapes.items()}


def _both_gradients(spec, cfg, batch, precision="float32"):
    weights = seeded(cfg)
    (mine, (_, aux)), g_mine = jax.value_and_grad(
        make_loss_fn(spec), has_aux=True)(
        as_tree(weights), {}, batch, jax.random.PRNGKey(0))
    theirs, g_ref = jax.value_and_grad(ref.loss)(
        weights, (batch[0], batch[1], None), cfg, precision)
    return float(mine), aux, by_path(g_mine), float(theirs), g_ref


@pytest.mark.parametrize("dtype,loss_tol,all_tol,leaf_tol", [
    # float32 against float32, reduction order only: the loss reads 1.2e-7
    # off, all entries 7.6e-7, the worst leaf (a head norm's scale) 1.5e-6
    (jnp.float32, 2e-6, 5e-6, 2e-5),
    # bfloat16 products against the float32 reference, 8 bits of mantissa
    # through 4 layers: all entries read 0.0070 off, the worst leaf 0.018
    # (a head norm's scale); the float8 control reads 0.069 over all
    # entries and 0.31 on its worst leaf, so the limits lie between
    (jnp.bfloat16, 1e-3, 0.025, 0.06),
])
def test_loss_and_every_leafs_gradient_against_the_reference(
        batch, dtype, loss_tol, all_tol, leaf_tol):
    """A dense layer, window layers, a full layer and half the experts."""
    spec, cfg = tiny(dtype=dtype)
    mine, aux, g_mine, theirs, g_ref = _both_gradients(spec, cfg, batch)
    assert abs(mine - theirs) <= loss_tol * theirs
    assert float(aux["ce_per_token"]) == mine
    assert set(g_mine) == set(g_ref)
    num = sum(float(jnp.sum((g_mine[p] - g_ref[p]) ** 2)) for p in g_ref)
    den = sum(float(jnp.sum(g_ref[p] ** 2)) for p in g_ref)
    assert math.sqrt(num / den) <= all_tol
    for p in g_ref:
        if p.endswith("router_bias"):
            # a selection has no gradient: exactly zero, in both
            assert not np.asarray(g_mine[p]).any()
            assert not np.asarray(g_ref[p]).any()
            continue
        assert float(jnp.linalg.norm(g_ref[p])) > 0, p
        gap = float(jnp.linalg.norm(g_mine[p] - g_ref[p])
                    / jnp.linalg.norm(g_ref[p]))
        assert gap <= leaf_tol, (p, gap)
    # the gate at seeded weights sits in the middle of its range
    assert 0.45 < float(aux["attn_gate_mean"]) < 0.55


def test_the_float8_control_is_further_from_the_program_than_float32(batch):
    spec, cfg = tiny(dtype=jnp.bfloat16)
    weights = seeded(cfg)
    g_mine = by_path(jax.grad(lambda p: make_loss_fn(spec)(
        p, {}, batch, jax.random.PRNGKey(0))[0])(as_tree(weights)))

    def err(precision):
        g = jax.grad(ref.loss)(weights, (batch[0], batch[1], None), cfg,
                               precision)
        num = sum(float(jnp.sum((g_mine[p] - g[p]) ** 2)) for p in g)
        return math.sqrt(num / sum(float(jnp.sum(g[p] ** 2)) for p in g))

    assert err("float8") > 3 * err("float32")
    assert err("float8") > 0.025        # the bfloat16 test's limit


def test_the_stream_starts_at_the_scaled_embedding(batch):
    """`mup_enabled`: the first layer sees `embedding[token] * sqrt(64)`;
    with it off the logits differ (the four norms a layer do not undo a
    scale of the stream: the residual carries it past them)."""
    spec, cfg = tiny()
    tree = as_tree(seeded(cfg))
    on = spec.module.apply({"params": tree}, batch[0])
    off = spec.module.clone(mup_enabled=False).apply({"params": tree},
                                                     batch[0])
    assert float(jnp.max(jnp.abs(on - off))) > 1e-3
    scaled = dict(tree, embed={"embedding": 8.0 * tree["embed"]["embedding"]})
    np.testing.assert_allclose(
        np.asarray(spec.module.clone(mup_enabled=False).apply(
            {"params": scaled}, batch[0])), np.asarray(on), atol=2e-5)


def _attention(window, positions, **kw):
    inv = rope.rope_inv_freq(16, 10000.0)
    return attention.Attention(4, 2, 16, window, tuple(inv.tolist()), 1.0,
                             False, jnp.float32, qk_norm=True,
                             qk_norm_eps=1e-5, positions=positions, **kw)


@pytest.mark.parametrize("kind", [SLIDING, FULL])
def test_a_layers_gated_attention_against_the_direct_formula(kind):
    """One `[S, S]` softmax a head at S = 32 from the layer's own weights:
    4 query heads in groups of 2 on 2 key/value heads of 16, each q and k
    head normed and scaled; in a window layer turned by half-split rotary
    at theta 10 000 and masked to the 8 keys `0 <= i - j < 8`, in a full
    layer NOT turned and masked to all earlier keys; the output times the
    sigmoid of the fifth projection, entry by entry, before `o_proj`."""
    s, h, heads, kv_heads, d, eps = 32, 64, 4, 2, 16, 1e-5
    _, cfg = tiny()
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, s, h)), jnp.float32)
    w = {p[len("attn/"):]: 5.0 * v for p, v in layer_weights(cfg, 1).items()
         if p.startswith("attn/")}
    w["q_layernorm"] = jnp.asarray(rng.uniform(0.5, 1.5, d), jnp.float32)
    w["k_layernorm"] = jnp.asarray(rng.uniform(0.5, 1.5, d), jnp.float32)
    window = WINDOW if kind == SLIDING else None
    got, gate_mean = _attention(window, kind == SLIDING, gate=True).apply(
        {"params": as_tree(w)}, x)

    def normed(v, scale):       # [S, d]
        return v / np.sqrt((v * v).mean(-1, keepdims=True) + eps) * scale

    def turn(v):                # [S, d]: pairs (j, j + d/2)
        if kind == FULL:
            return v
        out = np.array(v)
        for j in range(d // 2):
            ang = np.arange(s) * 10000.0 ** (-2 * j / d)
            a, b = v[:, j], v[:, j + d // 2]
            out[:, j] = a * np.cos(ang) - b * np.sin(ang)
            out[:, j + d // 2] = b * np.cos(ang) + a * np.sin(ang)
        return out

    back = np.arange(s)[:, None] - np.arange(s)[None, :]
    hidden = (back < 0) | ((back >= WINDOW) if kind == SLIDING else False)
    w64 = {p: np.asarray(v, np.float64) for p, v in w.items()}
    want, shares = np.zeros((2, s, h)), []
    for b in range(2):
        xb = np.asarray(x[b], np.float64)
        for i in range(heads):
            j = i // (heads // kv_heads)
            q = turn(normed(xb @ w64["q_proj/kernel"][:, i],
                            w64["q_layernorm"]))
            k = turn(normed(xb @ w64["k_proj/kernel"][:, j],
                            w64["k_layernorm"]))
            scores = q @ k.T / math.sqrt(d)
            scores[hidden] = -np.inf
            p = np.exp(scores - scores.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            share = 1.0 / (1.0 + np.exp(-(xb @ w64["gate_proj/kernel"][:, i])))
            shares.append(share)
            want[b] += (share * (p @ (xb @ w64["v_proj/kernel"][:, j]))) \
                @ w64["o_proj/kernel"][i]
    np.testing.assert_allclose(np.asarray(got), want, atol=3e-5)
    assert float(gate_mean) == pytest.approx(np.mean(shares), abs=1e-6)
    # and the reference's own, written apart, agrees
    np.testing.assert_allclose(
        np.asarray(ref.gated_attention(
            x, w, cfg, "float32", *ref.kind_as_data(cfg, kind, s))), want,
        atol=3e-5)
    # the gate, the mask and the turn each bite
    plain = _attention(window, kind == SLIDING).apply({"params": as_tree({
        p: v for p, v in w.items() if "gate_proj" not in p})}, x)
    assert float(jnp.max(jnp.abs(plain - got))) > 1e-2
    other = _attention(None if window else WINDOW, kind == SLIDING,
                       gate=True).apply({"params": as_tree(w)}, x)[0]
    assert float(jnp.max(jnp.abs(other - got))) > 1e-3
    turned = _attention(window, kind != SLIDING, gate=True).apply(
        {"params": as_tree(w)}, x)[0]
    assert float(jnp.max(jnp.abs(turned - got))) > 1e-3


def test_a_full_layer_knows_no_positions():
    """Without positions and without a window a token's output depends on
    WHICH tokens came before it and not on where they stood: the earlier
    positions of a sequence turned round leave a later position's output as
    it was; a window layer's moves."""
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(1, 12, 64)), jnp.float32)
    turned_round = x.at[:, :8].set(x[:, :8][:, ::-1])
    for window, positions, moved in ((None, False, False), (WINDOW, True,
                                                            True)):
        module = _attention(window, positions, gate=True)
        params = module.init(jax.random.PRNGKey(0), x)
        a = module.apply(params, x)[0][:, 8:]
        b = module.apply(params, turned_round)[0][:, 8:]
        assert (float(jnp.max(jnp.abs(a - b))) > 1e-4) == moved


@pytest.mark.parametrize("model,count,paths", [
    ("mellum2", 340349184, ["k_proj", "o_proj", "q_proj", "v_proj"]),
    ("lfm2_moe", 507820288, ["k_layernorm", "k_proj", "o_proj",
                             "q_layernorm", "q_proj", "v_proj"]),
    ("joyai_flash", 413959168, None)])
def test_the_accepted_models_keep_their_parameters(model, count, paths):
    """`Attention(gate=..., positions=...)` default to what the three
    accepted models have: the same leaves under `attn`, the same n at the
    benchmark's cut. (`JoyAIFlash` has its own latent attention.) Their
    compiled steps' temporaries, device-less for a v5e, parent against
    change: PERF.md section 6, PR 40."""
    with open(os.path.join(os.path.dirname(CONFIG), {
            "mellum2": "mellum2_12b_a2p5b", "lfm2_moe": "lfm2_8b_a1b",
            "joyai_flash": "joyai_llm_flash"}[model] + ".json")) as f:
        cfg = json.load(f)
    kw = {k: v for k, v in cfg["trainer"]["model_kwargs"].items()
          if k != "seq_len"}
    shapes = shapes_of(get_model(model, "ptb", vocab_size=cfg["vocab_size"],
                                 **kw), 128)
    assert sum(math.prod(s) for s in shapes.values()) == count \
        == cfg["arch"]["num_params"]
    assert not any("gate_proj" in p for p in shapes)
    if paths is not None:
        attn = {p.split("/")[2] for p in shapes if "/attn/" in p}
        assert sorted(attn) == paths


def _layer(share, shares, experts, top, kind, dense=False):
    model = tiny(share, shares, experts=experts, top=top)[0].module
    return afmoe.Layer(common.own_fields(model),
                       WINDOW if kind == SLIDING else None, dense)


@pytest.mark.parametrize("experts,top,shares,kind", [
    (128, 8, 16, SLIDING), (128, 8, 16, FULL), (8, 2, 2, SLIDING)])
def test_the_shares_add_up(experts, top, shares, kind):
    """Over all shares (the 16 shares of 8 of the cell's 128): the routed
    terms summed, with attention and the shared expert (which every chip
    computes alike) counted once, equal the uncut reference's layer BEFORE
    the norm that the branch leaves through; the counters count every
    assignment once. (The norm after the branch is not linear: a
    deployment sums the shares' parts first and norms the sum, so the sum
    is taken here of the branch as it enters that norm.)"""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(2, POSITIONS, 64)), jnp.float32)
    held = experts // shares
    index = 2 if kind == FULL else 1
    _, uncut = tiny(0, 1, experts=experts, top=top)
    weights = layer_weights(uncut, index)
    for p in ("moe/w1", "moe/w3", "moe/w2"):    # terms large enough to see
        weights[p] = 5.0 * weights[p]
    routed_paths = ("moe/w1", "moe/w3", "moe/w2")

    def branch(cfg, w):
        """The reference's MLP branch as it enters `post_mlp_norm`."""
        eps = cfg["rms_norm_eps"]
        u = ref.rms_norm(x, w["input_norm/scale"], eps)
        a = ref.gated_attention(u, ref._under(w, "attn/"), cfg, "float32",
                                *ref.kind_as_data(cfg, kind, POSITIONS))
        h = x + ref.rms_norm(a, w["post_attn_norm/scale"], eps)
        n = ref.rms_norm(h, w["pre_mlp_norm/scale"], eps).reshape(-1, 64)
        return h, ref.experts(n, ref._under(w, "moe/"), cfg, "float32")

    h_want, want = branch(uncut, weights)
    # what every share computes alike: the branch with no expert held
    nobody = dict(uncut, num_experts=0)
    _, alike = branch(nobody, {p: (v[:0] if p in routed_paths else v)
                               for p, v in weights.items()})
    routed, assigned = 0.0, 0.0
    for share in range(shares):
        mine = slice(share * held, (share + 1) * held)
        part = {p: (v[mine] if p in routed_paths else v)
                for p, v in weights.items()}
        _, cfg = tiny(share, shares, experts=experts, top=top)
        _, got = branch(cfg, part)
        routed = routed + (got - alike)
        # the program's layer is the reference's for this share, whole
        y, (counters, _) = _layer(share, shares, experts, top, kind).apply(
            {"params": as_tree(part)}, x)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(ref.layer(x, part, cfg, "float32",
                                                kind, False)), atol=5e-5)
        assigned += float(counters["moe_held_assignments"])
    assert assigned == 2 * POSITIONS * top
    assert float(jnp.max(jnp.abs(want - alike))) > 0.05
    np.testing.assert_allclose(np.asarray(routed + alike), np.asarray(want),
                               atol=5e-5)
    # and normed once, the sum is the uncut layer
    np.testing.assert_allclose(
        np.asarray(h_want + ref.rms_norm(
            (routed + alike).reshape(x.shape),
            weights["post_mlp_norm/scale"], 1e-5)),
        np.asarray(ref.layer(x, weights, uncut, "float32", kind, False)),
        atol=5e-5)


def test_the_chosen_scores_sum_with_the_constant_and_the_scale():
    """`route_norm` with 1e-20 in the sum, times `route_scale`: the weights
    add up to 2.826 (the constant is below float32's reach of a sum near
    4), chosen by score plus bias, weighted by the score alone."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(8, 128)), jnp.float32)
    bias = jnp.asarray(0.5 * rng.normal(size=(128,)), jnp.float32)
    cfg = {"num_experts_per_tok": 8, "route_scale": 2.826,
           "route_norm": True, "score_func": "sigmoid"}
    chosen, gates = ref.gates(x, router, bias, cfg)
    scores = jax.nn.sigmoid(x @ router)
    mine = moe.route(scores, 8, 0, 128, scores + bias, 2.826, 1e-20)
    group = np.empty(64 * 8, np.int64)
    group[np.asarray(mine[1])] = np.repeat(np.arange(128),
                                           np.asarray(mine[3]))
    np.testing.assert_array_equal(group.reshape(64, 8), np.asarray(chosen))
    np.testing.assert_allclose(np.asarray(mine[0]), np.asarray(gates),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 2.826, rtol=1e-6)
    plain = np.argsort(-np.asarray(scores), axis=-1)[:, :8]
    assert (np.sort(plain, -1) != np.sort(np.asarray(chosen), -1)).any()


@pytest.fixture(scope="module")
def op_names():
    """The `op_name` of every instruction of the COMPILED sparse step at
    tiny widths (`tests/test_model_scopes.py` compiles it)."""
    from test_model_scopes import compiled_op_names
    return compiled_op_names("afmoe")


def test_a_full_layers_compiled_program_has_no_rotary_turn(op_names):
    """The tiny model's layers are `sliding, sliding, full, sliding`:
    `rope` is on layers 0, 1 and 3 and not on layer 2, whose `attn_proj`
    holds the head norms, the scale and the gate all the same."""
    by_layer = {}
    for n in op_names:
        for i in range(4):
            if f"/layers_{i}/" in n:
                by_layer.setdefault(i, []).append(n)
    for i in (0, 1, 3):
        assert any("/rope/" in n for n in by_layer[i]), i
        assert any("/attn_window/" in n for n in by_layer[i]), i
    assert not any("/rope/" in n for n in by_layer[2])
    assert any("/attn_full/" in n for n in by_layer[2])
    for scope in ("/attn_proj/qk_norm/", "/attn_proj/attn_gate/"):
        assert any(scope in n for n in by_layer[2]), scope


def test_the_scopes_the_cells_readers_take_are_on_the_compiled_step(
        op_names):
    from benchmarks import model_scopes, scope_tree
    with open(CONFIG) as f:
        listed = json.load(f)["model_scopes"]
    by_scope = {}
    for name in op_names:
        scope = model_scopes.scope_of(name, listed)
        if scope:
            by_scope.setdefault(scope, []).append(name)
    assert set(by_scope) == {"attn_window", "attn_full", "attn_gate",
                             "moe_router", "moe_experts", "moe_shared",
                             "dense_mlp", "lm_head"} == set(listed)
    # the four norms a layer and the final one under the one name, never
    # the head norms; the embedding's scale inside `embed`
    norms = [n for n in op_names if "/rms_norm/" in n]
    assert {m for n in norms for m in ("input_norm", "post_attn_norm",
                                       "pre_mlp_norm", "post_mlp_norm")
            if f"/{m}/" in n} == {"input_norm", "post_attn_norm",
                                  "pre_mlp_norm", "post_mlp_norm"}
    assert not any("/qk_norm/" in n for n in norms)
    assert any("/embed/" in n and n.rsplit("/", 1)[-1].startswith("mul")
               for n in op_names)
    assert {scope_tree.parse(n)[1] for n in by_scope["moe_shared"]} == set(
        scope_tree.PASSES)


def test_an_unknown_name_lists_the_known_ones():
    with pytest.raises(ValueError, match="afmoe"):
        get_model("trinity", "ptb")
    with pytest.raises(ValueError, match="layer_types"):
        spec = get_model("afmoe", "ptb", vocab_size=VOCAB, num_layers=2,
                         layer_types=["conv", "sliding_attention"])
        spec.module.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 8), jnp.int32))


def test_through_the_trainer_for_a_few_sparse_steps(tmp_path):
    """`--dnn afmoe --dataset ptb` builds through `make_trainer` like every
    other model (the data set's cardinality reaches it as `vocab_size`),
    trains sparse steps on two workers under the default selector, and its
    `train` record carries the router's counters and the gate's mean."""
    from gaussiank_sgd_tpu import train
    kw = {"hidden_size": 64, "num_layers": 4,
          "layer_types": [PATTERN[i] for i in HELD], "num_dense_layers": 1,
          "dense_width": 96, "num_heads": 4, "num_kv_heads": 2,
          "head_dim": 16, "sliding_window": WINDOW, "num_experts": 8,
          "experts_per_token": 2, "expert_width": 32, "expert_share": 0,
          "expert_shares": 2, "seq_len": POSITIONS}
    data = {"vocab_size": VOCAB, "bptt": POSITIONS,
            "synthetic_tokens_n": 4 * (12 * POSITIONS + 1)}
    trainer = train.make_trainer([
        "--dnn", "afmoe", "--dataset", "ptb", "--nworkers", "2",
        "--batch-size", "2", "--compressor", "auto",
        "--density", "0.01",
        "--lr", "0.005", "--weight-decay", "0.0001", "--compute-dtype",
        "float32", "--max-steps", "8", "--log-every", "2",
        "--model-kwargs", json.dumps(kw), "--dataset-kwargs",
        json.dumps(data), "--output-dir", str(tmp_path)])
    try:
        assert trainer.spec.name == "afmoe"
        assert trainer.spec.module.vocab_size == VOCAB
        assert trainer._comp.name == "gaussian_fused"
        first = trainer.train(2)
        rec = trainer.train(4)
    finally:
        trainer.close()
    assert np.isfinite(rec["loss"]) and rec["loss"] < first["loss"] + 0.5
    assert rec["num_selected"] > 0
    # 2 sequences x 32 positions x top-2 a worker in 3 expert layers, half
    # the experts held
    assert 0 < rec["moe_held_assignments"] <= 3 * 2 * POSITIONS * 2
    assert rec["moe_load_max_over_mean"] >= 1.0
    assert 0.0 <= rec["moe_tokens_unserved"] < 1.0
    assert 0.4 < rec["attn_gate_mean"] < 0.6
    with open(os.path.join(trainer.run_dir, "metrics.jsonl")) as f:
        trains = [r for r in map(json.loads, f) if r.get("event") == "train"]
    assert trains and all("attn_gate_mean" in r for r in trains)


def test_the_attention_kernels_lower_for_the_tpu_without_positions():
    """Heads of 128, eight query heads to a key/value head, window and
    full: forward and backward lower to Mosaic calls (checked without a
    chip, as `tests/test_kernel_lowering.py` does; the numbers are the
    chip's to prove, by the cell's `correct`)."""
    s, kv_heads, group = 1024, 1, 8
    avals = [jax.ShapeDtypeStruct(shape, jnp.bfloat16) for shape in (
        (2, s, kv_heads, group, 128), (2, s, kv_heads, 128),
        (2, s, kv_heads, 128))]
    for window in (None, 512):
        def loss(q, k, v):
            return jnp.sum(attention.splash_attention(q, k, v, window)
                           .astype(jnp.float32))
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
            *avals).lower(lowering_platforms=("tpu",)).as_text()
        # a window layer keeps the dq kernel beside the dkv kernel, a full
        # layer's dkv kernel computes dq too
        assert text.count("tpu_custom_call") >= (3 if window else 2)
        for kernel in ("splash_mqa_fwd", "splash_mqa_dkv"):
            assert kernel in text
        assert ("splash_mqa_dq" in text) == bool(window)
