"""`models/lfm2_moe.py` at tiny widths on the CPU (hidden 64, a convolution
of 3 taps, 4 query and 2 key/value heads of 16 with normed heads, 8 experts
of 32 top-2 behind a sigmoid router with a selection bias, a leading dense
layer of 96, 32 positions, four layers `conv, full_attention, conv, conv`
cut from a longer pattern, the embedding for a head), against the
benchmark's plain reference (`benchmarks/reference/lfm2_8b_a1b.py`, which
imports nothing of the program) and against direct formulas."""

import json
import math
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import lfm2_8b_a1b as ref
from gaussiank_sgd_tpu import models
from gaussiank_sgd_tpu.models import get_model, lfm2_moe
from gaussiank_sgd_tpu.models.blocks import attention, common, rope
from gaussiank_sgd_tpu.models.blocks import experts as moe
from gaussiank_sgd_tpu.training.losses import make_loss_fn
from test_joyai_flash import as_tree, by_path, shapes_of

VOCAB, POSITIONS = 50, 32
# the pattern the tiny model is cut from, and which of its layers are held
PATTERN = ["conv", "conv", "full_attention", "conv", "conv", "conv"]
HELD = [0, 2, 3, 4]
CONFIG = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                      "configs", "lfm2_8b_a1b.json")


def tiny(share=0, shares=2, dtype=jnp.float32, experts=8, top=2, held=HELD,
         dense_layers=1):
    """(the program's model, the reference's configuration) of one share."""
    spec = get_model(
        "lfm2_moe", "ptb", vocab_size=VOCAB, dtype=dtype, hidden_size=64,
        num_layers=len(held), layer_types=[PATTERN[i] for i in held],
        num_dense_layers=dense_layers, dense_width=96, num_heads=4,
        num_kv_heads=2, head_dim=16, num_experts=experts,
        experts_per_token=top, expert_width=32, expert_share=share,
        expert_shares=shares)
    cfg = {"hidden_size": 64, "num_hidden_layers": len(held),
           "layer_types": PATTERN, "num_dense_layers": dense_layers,
           "intermediate_size": 96, "conv_L_cache": 3, "conv_bias": False,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 16, "rope_theta": 1000000, "norm_eps": 1e-5,
           "num_experts": experts // shares, "num_experts_per_tok": top,
           "moe_intermediate_size": 32, "routed_scaling_factor": 1,
           "norm_topk_prob": True, "use_expert_bias": True,
           "vocab_size": VOCAB, "published": {"num_experts": experts},
           "share": {"expert_share": share, "expert_shares": shares,
                     "layers": held}}
    return spec, cfg


def seeded(cfg, key=7):
    """The reference's seeded weights with a NON-ZERO selection bias, of
    the size of the scores' spread (logits of 0.16 at these widths, so
    scores of 0.04): it changes which experts are chosen, and does not
    choose alone."""
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
    assert "lfm2_moe" in models.NAMES and spec.task == "lm" and spec.counters
    assert spec.mtp_lambda == 0.0
    mine = shapes_of(spec)
    assert mine == {p: tuple(s) for p, s in ref.param_shapes(cfg).items()}
    # one leaf is embedding and head; the kinds of module by layer
    assert "lm_head" not in mine
    assert mine["layers_0/conv/taps"] == (64, 3)
    assert "layers_0/mlp/w1" in mine and "layers_1/moe/w1" in mine
    assert mine["layers_1/attn/q_layernorm"] == (16,)
    assert not any(p.startswith("layers_1/conv/") for p in mine)


def test_every_token_model_is_named_where_the_models_are():
    """The trainer asks `models.TOKEN_MODELS` which models take the data
    set's cardinality as `vocab_size`: every name of `NAMES` whose task
    reads token ids is there, and no other."""
    want = set()
    for name in models.NAMES:
        spec = get_model(name, "ptb")
        if spec.task in ("lm", "seq2seq"):
            want.add(name)
    assert want <= models.TOKEN_MODELS
    assert {get_model(n, "ptb").name for n in models.TOKEN_MODELS} == want


def test_published_widths_give_both_parameter_counts():
    """The benchmark's cut (layers 0 and 2-5 of 24, 8 of 32 experts, 16 384
    rows of 65 536) at the published widths, from shapes alone; and the
    whole published model, 8.34 B with the one tied leaf."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    assert cfg["arch"]["num_params"] == 507820288
    assert sum(math.prod(s) for s in ref.param_shapes(cfg).values()) \
        == 507820288
    kw = {k: v for k, v in cfg["trainer"]["model_kwargs"].items()
          if k != "seq_len"}
    spec = get_model("lfm2_moe", "ptb", vocab_size=cfg["vocab_size"], **kw)
    assert shapes_of(spec, 128) == {
        p: tuple(s) for p, s in ref.param_shapes(cfg).items()}
    assert [spec.module.layer_types[i] for i in range(5)] == [
        cfg["layer_types"][i] for i in cfg["share"]["layers"]]
    # every width is the published one
    m = spec.module
    assert (m.hidden_size, m.num_heads, m.num_kv_heads, m.head_dim,
            m.expert_width, m.dense_width, m.num_experts,
            m.experts_per_token, m.conv_taps, m.rope_theta, m.rms_norm_eps,
            m.routed_scaling_factor) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"],
        cfg["moe_intermediate_size"], cfg["intermediate_size"],
        cfg["published"]["num_experts"], cfg["num_experts_per_tok"],
        cfg["conv_L_cache"], cfg["rope_theta"], cfg["norm_eps"],
        cfg["routed_scaling_factor"]) == (
        2048, 32, 8, 64, 1792, 7168, 32, 4, 3, 1e6, 1e-5, 1)
    # the whole model: the program's defaults are the published config
    whole = dict(cfg, **cfg["published"])
    whole["share"] = {"expert_share": 0, "expert_shares": 1,
                      "layers": list(range(24))}
    count = sum(math.prod(s) for s in ref.param_shapes(whole).values())
    assert count == 8339930560
    assert shapes_of(get_model("lfm2_moe", "ptb"), 128) == {
        p: tuple(s) for p, s in ref.param_shapes(whole).items()}


def _both_gradients(spec, cfg, batch, precision="float32"):
    weights = seeded(cfg)
    (mine, (_, aux)), g_mine = jax.value_and_grad(
        make_loss_fn(spec), has_aux=True)(
        as_tree(weights), {}, batch, jax.random.PRNGKey(0))
    theirs, g_ref = jax.value_and_grad(ref.loss)(
        weights, (batch[0], batch[1], None), cfg, precision)
    return float(mine), aux, by_path(g_mine), float(theirs), g_ref


@pytest.mark.parametrize("dtype,loss_tol,all_tol,leaf_tol", [
    # float32 against float32, reduction order only: the loss reads 6e-8
    # off, all entries 2.4e-8, the worst leaf (a norm's scale) 3.8e-7 of
    # its norm
    (jnp.float32, 2e-6, 2e-6, 1e-5),
    # bfloat16 products against the float32 reference, 8 bits of mantissa
    # through 4 layers: the loss reads 1.5e-4 off (a token's own logit is
    # about the hidden size at seeded weights, so the loss is large and its
    # rounding with it), all entries 0.0019, the worst leaf (a norm's
    # scale) 0.011; the float8 control reads 0.0195 over all entries and
    # 0.19 in its worst leaf, so the limits lie between
    (jnp.bfloat16, 1e-3, 0.006, 0.04),
])
def test_loss_and_every_leafs_gradient_against_the_reference(
        batch, dtype, loss_tol, all_tol, leaf_tol):
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

    assert err("float8") > 4 * err("float32")
    assert err("float8") > 0.006        # the bfloat16 test's limit


def test_the_tied_leafs_gradient_is_the_sum_of_both_paths(batch):
    """`embed/embedding` is read twice, as the table the tokens' rows come
    from and as the head. With the two uses handed in as two arguments the
    gradients by each are both far from zero, the table's touches only the
    rows the batch names, and their sum is the tied leaf's gradient, in
    the program and in the reference alike."""
    spec, cfg = tiny()
    weights = seeded(cfg)
    tree = as_tree(weights)

    def untied(table, head):
        # the stream starts from `table`'s rows, the head is `head`
        logits = _logits_from_rows(
            spec, dict(tree, embed={"embedding": head}), table[batch[0]])
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, batch[1][..., None],
                                     axis=-1)[..., 0]
        return jnp.mean(lse - picked)

    e = weights["embed/embedding"]
    by_table, by_head = jax.grad(untied, argnums=(0, 1))(e, e)
    named = np.zeros(VOCAB, bool)
    named[np.unique(np.asarray(batch[0]))] = True
    assert not np.asarray(by_table)[~named].any()
    assert np.asarray(by_head)[~named].any()      # the head sees every row
    for part in (by_table, by_head):
        assert float(jnp.linalg.norm(part)) > 0.05 * float(
            jnp.linalg.norm(by_table + by_head))
    tied = by_path(jax.grad(lambda p: make_loss_fn(spec)(
        p, {}, batch, jax.random.PRNGKey(0))[0])(tree))["embed/embedding"]
    np.testing.assert_allclose(np.asarray(tied),
                               np.asarray(by_table + by_head), atol=2e-6)
    g_ref = jax.grad(ref.loss)(weights, (batch[0], batch[1], None), cfg)
    np.testing.assert_allclose(np.asarray(g_ref["embed/embedding"]),
                               np.asarray(by_table + by_head), atol=2e-6)


def _logits_from_rows(spec, tree, rows):
    """The model's logits with the embedding's rows given: its layers, its
    final norm and its head applied by hand from the model's own modules."""
    m = spec.module
    widths = common.own_fields(m)
    x = rows
    for i, kind in enumerate(m.layer_types):
        x, _ = lfm2_moe.Layer(widths, kind, i < m.num_dense_layers).apply(
            {"params": tree[f"layers_{i}"]}, x)
    x = common.RMSNorm(m.rms_norm_eps, m.dtype).apply(
        {"params": tree["embedding_norm"]}, x)
    return jnp.einsum("bsh,vh->bsv", x, tree["embed"]["embedding"])


def _fields(**kw):
    base = dict(conv_taps=3, dtype=jnp.float32)
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_the_convolution_against_a_direct_triple_loop():
    """Batch, position, channel: `c_t = k_0 v_{t-2} + k_1 v_{t-1} + k_2
    v_t` between the two gates, zeros before the sequence starts."""
    rng = np.random.default_rng(4)
    b, s, h = 2, 9, 8
    x = rng.normal(size=(b, s, h)).astype(np.float32)
    w = {"in_proj": {"kernel": rng.normal(size=(h, 3 * h)).astype(np.float32)},
         "taps": rng.normal(size=(h, 3)).astype(np.float32),
         "out_proj": {"kernel": rng.normal(size=(h, h)).astype(np.float32)}}
    got = np.asarray(lfm2_moe.ShortConv(_fields()).apply(
        {"params": w}, jnp.asarray(x)))
    bcx = x.astype(np.float64) @ w["in_proj"]["kernel"]
    gate_in, gate_out, u = bcx[..., :h], bcx[..., h:2 * h], bcx[..., 2 * h:]
    v = gate_in * u
    y = np.zeros((b, s, h))
    for i in range(b):
        for t in range(s):
            for ch in range(h):
                c = sum(w["taps"][ch, j] * v[i, t - 2 + j, ch]
                        for j in range(3) if t - 2 + j >= 0)
                y[i, t, ch] = gate_out[i, t, ch] * c
    want = y @ w["out_proj"]["kernel"]
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # and the reference's own, written apart, agrees
    flat = {"in_proj/kernel": w["in_proj"]["kernel"], "taps": w["taps"],
            "out_proj/kernel": w["out_proj"]["kernel"]}
    np.testing.assert_allclose(
        np.asarray(ref.short_conv(jnp.asarray(x), flat, {"conv_L_cache": 3},
                                  "float32")), want, rtol=2e-5, atol=2e-5)


def test_the_convolution_is_causal():
    """Position t is unmoved by inputs after t, forward; and no gradient
    flows from an output at t to an input after it."""
    rng = np.random.default_rng(6)
    s, h, t = 12, 8, 5
    module = lfm2_moe.ShortConv(_fields())
    x = jnp.asarray(rng.normal(size=(1, s, h)), jnp.float32)
    params = module.init(jax.random.PRNGKey(0), x)
    params = jax.tree.map(lambda v: 10.0 * v, params)
    later = x.at[:, t + 1:].set(jnp.asarray(
        rng.normal(size=(1, s - t - 1, h)), jnp.float32))
    a, b = module.apply(params, x), module.apply(params, later)
    np.testing.assert_array_equal(np.asarray(a[:, :t + 1]),
                                  np.asarray(b[:, :t + 1]))
    assert np.abs(np.asarray(a[:, t + 1:] - b[:, t + 1:])).max() > 1e-3
    g = jax.grad(lambda x: jnp.sum(module.apply(params, x)[:, t]))(x)
    assert not np.asarray(g[:, t + 1:]).any()
    assert np.asarray(g[:, t - 2:t + 1]).all()      # its three taps' reach
    assert not np.asarray(g[:, :t - 2]).any()


def test_normed_heads_against_the_direct_formula():
    """One `[S, S]` softmax a head at S = 32 from the layer's own weights:
    4 query heads in groups of 2 on 2 key/value heads of 16, each q and k
    head divided by its root mean square and multiplied by the learned
    scale BEFORE the half-split rotary turn."""
    s, h, heads, kv_heads, d, eps = 32, 64, 4, 2, 16, 1e-5
    _, cfg = tiny(held=[2], dense_layers=0)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, s, h)), jnp.float32)
    w = {p[len("attn/"):]: v for p, v in layer_weights(cfg, 0).items()
         if p.startswith("attn/")}
    w["q_layernorm"] = jnp.asarray(rng.uniform(0.5, 1.5, d), jnp.float32)
    w["k_layernorm"] = jnp.asarray(rng.uniform(0.5, 1.5, d), jnp.float32)
    inv = rope.rope_inv_freq(d, 1e6)
    got = attention.Attention(
        heads, kv_heads, d, None, tuple(inv.tolist()), 1.0, False,
        jnp.float32, qk_norm=True, qk_norm_eps=eps).apply(
            {"params": as_tree(w)}, x)

    def normed(v, scale):       # [S, d]
        return v / np.sqrt((v * v).mean(-1, keepdims=True) + eps) * scale

    def turn(v):                # [S, d]: pairs (j, j + d/2)
        out = np.array(v)
        for j in range(d // 2):
            ang = np.arange(s) * 1e6 ** (-2 * j / d)
            a, b = v[:, j], v[:, j + d // 2]
            out[:, j] = a * np.cos(ang) - b * np.sin(ang)
            out[:, j + d // 2] = b * np.cos(ang) + a * np.sin(ang)
        return out

    w = {p: np.asarray(v, np.float64) for p, v in w.items()}
    want = np.zeros((2, s, h))
    for b in range(2):
        xb = np.asarray(x[b], np.float64)
        for i in range(heads):
            j = i // (heads // kv_heads)
            q = turn(normed(xb @ w["q_proj/kernel"][:, i], w["q_layernorm"]))
            k = turn(normed(xb @ w["k_proj/kernel"][:, j], w["k_layernorm"]))
            scores = q @ k.T / math.sqrt(d)
            scores[np.triu_indices(s, 1)] = -np.inf
            p = np.exp(scores - scores.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            want[b] += (p @ (xb @ w["v_proj/kernel"][:, j])) \
                @ w["o_proj/kernel"][i]
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    # the norm bites: without it the layer answers otherwise
    plain = attention.Attention(
        heads, kv_heads, d, None, tuple(inv.tolist()), 1.0, False,
        jnp.float32).apply({"params": as_tree({
            p: v for p, v in w.items() if "layernorm" not in p})}, x)
    assert float(jnp.max(jnp.abs(plain - got))) > 1e-3


def test_without_normed_heads_the_attention_has_its_old_parameters():
    shapes = jax.eval_shape(
        lambda x: attention.Attention(4, 2, 16, None, (1.0,) * 8, 1.0, False,
                                    jnp.float32).init(
                                        jax.random.PRNGKey(0), x),
        jnp.zeros((1, 8, 64)))["params"]
    assert sorted(shapes) == ["k_proj", "o_proj", "q_proj", "v_proj"]


def test_selection_follows_score_plus_bias_and_weights_the_scores_alone():
    rng = np.random.default_rng(2)
    scores = jax.nn.sigmoid(jnp.asarray(rng.normal(size=(64, 32)),
                                        jnp.float32))
    bias = jnp.asarray(0.5 * rng.normal(size=(32,)), jnp.float32)
    top = 4
    weights, order, inverse, sizes, served = moe.route(
        scores, top, 0, 32, scores + bias, 1.0, 1e-6)
    want = np.argsort(-np.asarray(scores + bias), axis=-1)[:, :top]
    plain = np.argsort(-np.asarray(scores), axis=-1)[:, :top]
    assert (np.sort(want, -1) != np.sort(plain, -1)).any()   # the bias bites
    # assignment a = token * top + slot went to expert `group[a]`
    group = np.empty(64 * top, np.int64)
    group[np.asarray(order)] = np.repeat(np.arange(32), np.asarray(sizes))
    np.testing.assert_array_equal(np.sort(group.reshape(64, top), -1),
                                  np.sort(want, -1))
    picked = np.take_along_axis(np.asarray(scores, np.float64),
                                group.reshape(64, top), axis=-1)
    np.testing.assert_allclose(
        np.asarray(weights), picked / (picked.sum(-1, keepdims=True) + 1e-6),
        rtol=1e-6)
    # the constant in the sum is there: the weights add up to less than 1
    # by it (in float64 from the float32 weights; no constant gives 1)
    short = 1.0 - np.asarray(weights, np.float64).sum(-1)
    np.testing.assert_allclose(short, 1e-6 / (picked.sum(-1) + 1e-6),
                               atol=2e-7)
    assert bool(served.all())
    # and the reference's own gates, written apart, agree
    x = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(8, 32)), jnp.float32)
    chosen, gates = ref.gates(x, router, bias, {
        "num_experts_per_tok": top, "routed_scaling_factor": 1})
    mine = moe.route(jax.nn.sigmoid(x @ router), top, 0, 32,
                         jax.nn.sigmoid(x @ router) + bias, 1.0, 1e-6)
    group[np.asarray(mine[1])] = np.repeat(np.arange(32),
                                           np.asarray(mine[3]))
    np.testing.assert_array_equal(group.reshape(64, top), np.asarray(chosen))
    np.testing.assert_allclose(np.asarray(mine[0]), np.asarray(gates),
                               rtol=1e-6)


def _layer(share, shares, experts, top, kind="conv"):
    model = tiny(share, shares, experts=experts, top=top)[0].module
    return lfm2_moe.Layer(common.own_fields(model), kind, False)


@pytest.mark.parametrize("experts,top,shares,kind", [
    (32, 4, 4, "conv"), (32, 4, 4, "full_attention"), (8, 2, 2, "conv")])
def test_the_shares_add_up(experts, top, shares, kind):
    """Over all shares (the four shares of 8 of the cell's 32): the routed
    terms summed, with the mixer and the norms (which every chip computes
    alike) counted once, equal the uncut reference's layer; the counters
    count every assignment once."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(2, POSITIONS, 64)), jnp.float32)
    held = experts // shares
    index = 1 if kind == "full_attention" else 2
    _, uncut = tiny(0, 1, experts=experts, top=top)
    weights = layer_weights(uncut, index)
    for p in ("moe/w1", "moe/w3", "moe/w2"):    # terms large enough to see
        weights[p] = 5.0 * weights[p]
    want = ref.layer(x, weights, uncut, "float32", kind, False)
    # what every share computes alike: the layer with no expert held
    nobody = dict(uncut, num_experts=0)
    alike = ref.layer(x, {p: (v[:0] if p in ("moe/w1", "moe/w3", "moe/w2")
                              else v) for p, v in weights.items()},
                      nobody, "float32", kind, False)
    routed, assigned = 0.0, 0.0
    for share in range(shares):
        mine = slice(share * held, (share + 1) * held)
        part = {p: (v[mine] if p in ("moe/w1", "moe/w3", "moe/w2") else v)
                for p, v in weights.items()}
        y, counters = _layer(share, shares, experts, top, kind).apply(
            {"params": as_tree(part)}, x)
        routed = routed + (y - alike)
        assigned += float(counters["moe_held_assignments"])
    assert assigned == 2 * POSITIONS * top
    assert float(jnp.max(jnp.abs(want - alike))) > 0.05
    np.testing.assert_allclose(np.asarray(routed + alike), np.asarray(want),
                               atol=5e-5)


def test_no_token_is_dropped_when_every_token_goes_to_one_held_expert():
    """A selection bias that sends every token to expert 1 (and its other
    choices wherever the scores put them): expert 1 gets all T rows, more
    than twice an even load's, and the layer's output is the reference's."""
    experts, top, shares = 32, 4, 4
    rng = np.random.default_rng(13)
    x = jnp.asarray(rng.normal(size=(2, POSITIONS, 64)), jnp.float32)
    _, cfg = tiny(0, shares, experts=experts, top=top)
    weights = layer_weights(cfg, 2)
    weights["moe/router_bias"] = jnp.zeros(experts).at[1].set(10.0)
    y, counters = _layer(0, shares, experts, top).apply(
        {"params": as_tree(weights)}, x)
    tokens = 2 * POSITIONS
    assert float(counters["moe_held_assignments"]) >= tokens
    assert float(counters["moe_tokens_unserved"]) == 0.0
    assert float(counters["moe_load_max_over_mean"]) == pytest.approx(
        tokens / (float(counters["moe_held_assignments"])
                  / (experts // shares)))
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(ref.layer(x, weights, cfg, "float32",
                                            "conv", False)), atol=5e-5)


@pytest.fixture(scope="module")
def op_names():
    """The `op_name` of every instruction of the COMPILED sparse step at
    tiny widths (`tests/test_model_scopes.py` compiles it)."""
    from test_model_scopes import compiled_op_names
    return compiled_op_names("lfm2_moe")


def test_the_mixers_scopes_are_on_every_pass_of_the_compiled_step(op_names):
    """`short_conv` encloses `conv_in_proj`, `conv_gate`, `conv_out_proj`,
    each on forward, recomputed and backward instructions, read the way
    the benchmark reads them: the innermost name of the configuration's
    `model_scopes` (`benchmarks/model_scopes.scope_of`)."""
    from benchmarks import model_scopes, scope_tree
    with open(CONFIG) as f:
        listed = json.load(f)["model_scopes"]
    by_scope = {}
    for name in op_names:
        scope = model_scopes.scope_of(name, listed)
        if scope:
            by_scope.setdefault(scope, []).append(name)
    for scope in ("conv_in_proj", "conv_gate", "conv_out_proj"):
        names = by_scope[scope]
        assert {scope_tree.parse(n)[1] for n in names} == set(
            scope_tree.PASSES), scope
        for n in names:
            parts = n.split("/")
            assert parts.index("short_conv") < parts.index(scope), n
    # the scopes the cell's older readers take are there as before
    assert {"attn_full", "moe_router", "moe_experts", "dense_mlp",
            "lm_head"} <= set(by_scope)


def test_the_heads_norm_is_inside_the_projections_and_under_no_norms_name(
        op_names):
    """`qk_norm` lies inside `attn_proj` on every pass and never under
    `rms_norm`, so `attn_proj_ms` holds it and `rms_norm_ms` does not."""
    from benchmarks import scope_tree
    mine = [n for n in op_names if "/qk_norm/" in n]
    assert {scope_tree.parse(n)[1] for n in mine} == set(scope_tree.PASSES)
    for n in mine:
        chain = scope_tree.parse(n)[0]
        assert "attn_proj" in chain and "rms_norm" not in chain, n


def test_an_unknown_name_lists_the_known_ones():
    with pytest.raises(ValueError, match="lfm2_moe"):
        get_model("lfm2", "ptb")
    with pytest.raises(ValueError, match="layer_types"):
        spec = get_model("lfm2_moe", "ptb", vocab_size=VOCAB, num_layers=2,
                         layer_types=["conv", "sliding_attention"])
        spec.module.init(jax.random.PRNGKey(0),
                         jnp.zeros((1, 8), jnp.int32))


def test_through_the_trainer_for_a_few_sparse_steps(tmp_path):
    """`--dnn lfm2_moe --dataset ptb` builds through `make_trainer` like
    every other model (the data set's cardinality reaches it as
    `vocab_size`), trains sparse steps on two workers under the default
    selector, and its `train` record carries the router's counters."""
    from gaussiank_sgd_tpu import train
    kw = {"hidden_size": 64, "num_layers": 4,
          "layer_types": [PATTERN[i] for i in HELD], "num_dense_layers": 1,
          "dense_width": 96, "num_heads": 4, "num_kv_heads": 2,
          "head_dim": 16, "num_experts": 8, "experts_per_token": 2,
          "expert_width": 32, "expert_share": 0, "expert_shares": 2,
          "seq_len": POSITIONS}
    data = {"vocab_size": VOCAB, "bptt": POSITIONS,
            "synthetic_tokens_n": 4 * (12 * POSITIONS + 1)}
    trainer = train.make_trainer([
        "--dnn", "lfm2_moe", "--dataset", "ptb", "--nworkers", "2",
        "--batch-size", "2", "--compressor", "auto",
        "--density", "0.01",
        "--lr", "0.005", "--weight-decay", "0.0001", "--compute-dtype",
        "float32", "--max-steps", "8", "--log-every", "2",
        "--model-kwargs", json.dumps(kw), "--dataset-kwargs",
        json.dumps(data), "--output-dir", str(tmp_path)])
    try:
        assert trainer.spec.name == "lfm2_moe"
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
    with open(os.path.join(trainer.run_dir, "metrics.jsonl")) as f:
        trains = [r for r in map(json.loads, f) if r.get("event") == "train"]
    assert trains and all("moe_load_max_over_mean" in r for r in trains)


def test_the_attention_kernels_lower_for_the_tpu_at_heads_of_64():
    """Query and key/value heads of 64, four query heads to a key/value
    head: forward and backward lower to Mosaic calls, the backward pass of
    this full layer to the dkv kernel alone, which computes dq too (checked
    without a chip, as `tests/test_kernel_lowering.py` does; the numbers
    are the chip's to prove, by the cell's `correct`)."""
    def loss(q, k, v):
        return jnp.sum(attention.splash_attention(q, k, v, None)
                       .astype(jnp.float32))

    s, kv_heads, group = 1024, 2, 4
    avals = [jax.ShapeDtypeStruct(shape, jnp.bfloat16) for shape in (
        (2, s, kv_heads, group, 64), (2, s, kv_heads, 64),
        (2, s, kv_heads, 64))]
    out = jax.eval_shape(attention.splash_attention, *avals, None)
    assert out.shape == (2, s, kv_heads, group, 64)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(*avals).lower(
        lowering_platforms=("tpu",)).as_text()
    # a full layer: the forward kernel and the ONE fused backward kernel
    assert text.count("tpu_custom_call") >= 2
    for kernel in ("splash_mqa_fwd", "splash_mqa_dkv"):
        assert kernel in text
    assert "splash_mqa_dq" not in text
