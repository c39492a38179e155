"""`models/joyai_flash.py` at tiny widths on the CPU (hidden 64, 4 heads of
16 + 8 against values of 16, query rank 48, latent rank 32, 8 experts of 32
top-2 times 2.5 with a shared expert, a leading dense layer of 96, 32
positions, 3 layers), against the benchmark's plain reference
(`benchmarks/reference/joyai_llm_flash.py`, which imports nothing of the
program) and against direct formulas."""

import json
import math
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import joyai_llm_flash as ref
from gaussiank_sgd_tpu.models import NAMES, get_model, joyai_flash
from gaussiank_sgd_tpu.models.blocks import attention, common, rope
from gaussiank_sgd_tpu.models.blocks import experts as moe
from gaussiank_sgd_tpu.training.losses import make_loss_fn

VOCAB, POSITIONS, MTP_LAMBDA = 50, 32, 0.3
CONFIG = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                      "configs", "joyai_llm_flash.json")


def tiny(share=0, shares=2, dtype=jnp.float32, mtp=0, experts=8, top=2,
         layers=3):
    """(the program's model, the reference's configuration) of one share."""
    kw = dict(
        hidden_size=64, num_layers=layers, first_k_dense_replace=1,
        dense_width=96, num_heads=4, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        num_experts=experts, experts_per_token=top, expert_width=32,
        expert_share=share, expert_shares=shares,
        num_nextn_predict_layers=mtp, mtp_lambda=MTP_LAMBDA)
    spec = get_model("joyai_flash", "ptb", vocab_size=VOCAB, dtype=dtype,
                     **kw)
    cfg = {"hidden_size": 64, "num_hidden_layers": layers,
           "first_k_dense_replace": 1, "intermediate_size": 96,
           "num_attention_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
           "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
           "rope_theta": 32000000, "rope_interleave": True,
           "rope_scaling": None, "n_routed_experts": experts // shares,
           "num_experts_per_tok": top, "moe_intermediate_size": 32,
           "n_shared_experts": 1, "routed_scaling_factor": 2.5,
           "scoring_func": "sigmoid", "norm_topk_prob": True,
           "vocab_size": VOCAB, "rms_norm_eps": 1e-6,
           "num_nextn_predict_layers": mtp, "mtp_lambda": MTP_LAMBDA,
           "published": {"n_routed_experts": experts},
           "share": {"expert_share": share, "expert_shares": shares}}
    return spec, cfg


def by_path(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(str(k.key) for k in p): v for p, v in flat}


def as_tree(flat):
    out = {}
    for path, v in flat.items():
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return out


def shapes_of(spec, positions=POSITIONS):
    shapes = jax.eval_shape(
        lambda x: spec.module.init(jax.random.PRNGKey(0), x, train=False),
        jnp.zeros((1, positions), jnp.int32))["params"]
    return {p: tuple(v.shape) for p, v in by_path(shapes).items()}


def seeded(cfg, key=7):
    """The reference's seeded weights with a NON-ZERO selection bias, of
    the size of the scores' spread: it changes which experts are chosen."""
    weights = ref.init_params(jax.random.PRNGKey(key), cfg)
    for i, p in enumerate(sorted(weights)):
        if p.endswith("router_bias"):
            weights[p] = 0.2 * jax.random.normal(
                jax.random.PRNGKey(100 + i), weights[p].shape)
    return weights


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(3)
    toks = rng.integers(0, VOCAB, (2, POSITIONS + 1)).astype(np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


@pytest.mark.parametrize("mtp", [0, 1])
def test_parameter_paths_are_the_references(mtp):
    spec, cfg = tiny(mtp=mtp)
    assert "joyai_flash" in NAMES and spec.task == "lm" and spec.counters
    assert spec.mtp_lambda == (MTP_LAMBDA if mtp else 0.0)
    assert shapes_of(spec) == {p: tuple(s)
                               for p, s in ref.param_shapes(cfg).items()}
    assert any(p.startswith("mtp_block/moe/") for p in shapes_of(spec)) == (
        mtp == 1)


@pytest.mark.parametrize("mtp,count", [(0, 413959168), (1, 491697408)])
def test_published_widths_give_the_cells_parameter_count(mtp, count):
    """The benchmark's cut (5 layers, 8 of 256 experts, 16 160 rows) at the
    published widths, from shapes alone; and with the prediction module,
    which the chip's configuration leaves to the last pipeline stage."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    assert cfg["num_nextn_predict_layers"] == 0
    assert cfg["arch"]["num_params"] == 413959168
    cfg["num_nextn_predict_layers"] = mtp
    assert sum(math.prod(s) for s in ref.param_shapes(cfg).values()) == count
    kw = dict(cfg["trainer"]["model_kwargs"], num_nextn_predict_layers=mtp)
    spec = get_model("joyai_flash", "ptb", vocab_size=cfg["vocab_size"],
                     **{k: v for k, v in kw.items() if k != "seq_len"})
    assert shapes_of(spec, 128) == {
        p: tuple(s) for p, s in ref.param_shapes(cfg).items()}
    # every width is the published one
    m = spec.module
    assert (m.hidden_size, m.num_heads, m.q_lora_rank, m.kv_lora_rank,
            m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim,
            m.expert_width, m.dense_width, m.num_experts,
            m.experts_per_token, m.shared_experts, m.routed_scaling_factor,
            m.rope_theta, m.rope_interleave, m.first_k_dense_replace) == (
        cfg["hidden_size"], cfg["num_attention_heads"], cfg["q_lora_rank"],
        cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
        cfg["qk_rope_head_dim"], cfg["v_head_dim"],
        cfg["moe_intermediate_size"], cfg["intermediate_size"],
        cfg["published"]["n_routed_experts"], cfg["num_experts_per_tok"],
        cfg["n_shared_experts"], cfg["routed_scaling_factor"],
        cfg["rope_theta"], cfg["rope_interleave"],
        cfg["first_k_dense_replace"]) == (
        2048, 32, 1536, 512, 128, 64, 128, 768, 7168, 256, 8, 1, 2.5, 32e6,
        True, 1)
    assert cfg["qk_head_dim"] == 192 == m.qk_nope_head_dim + m.qk_rope_head_dim


def _both_gradients(spec, cfg, batch, precision="float32"):
    weights = seeded(cfg)
    (mine, (_, aux)), g_mine = jax.value_and_grad(
        make_loss_fn(spec), has_aux=True)(
        as_tree(weights), {}, batch, jax.random.PRNGKey(0))
    theirs, g_ref = jax.value_and_grad(ref.loss)(
        weights, (batch[0], batch[1], None), cfg, precision)
    return float(mine), aux, by_path(g_mine), float(theirs), g_ref


@pytest.mark.parametrize("mtp", [0, 1])
@pytest.mark.parametrize("dtype,loss_tol,all_tol,leaf_tol", [
    # float32 against float32, reduction order only: the loss reads 1.2e-7
    # off, all entries 6e-8, the worst leaf (a router) 4.7e-7 of its norm
    (jnp.float32, 2e-6, 2e-6, 1e-5),
    # bfloat16 products against the float32 reference, 8 bits of mantissa
    # through 3 layers (4 with the module): the loss reads 3.1e-5 off, all
    # entries 0.0049, the worst leaf (a norm's scale, a router) 0.014; the
    # float8 control reads 0.031 over all entries at least, so the limits
    # lie between
    (jnp.bfloat16, 5e-4, 0.012, 0.03),
])
def test_loss_and_every_leafs_gradient_against_the_reference(
        batch, mtp, dtype, loss_tol, all_tol, leaf_tol):
    spec, cfg = tiny(dtype=dtype, mtp=mtp)
    mine, aux, g_mine, theirs, g_ref = _both_gradients(spec, cfg, batch)
    assert abs(mine - theirs) <= loss_tol * theirs
    assert set(g_mine) == set(g_ref)
    if mtp:
        # the loss is the main cross-entropy and lambda times the module's
        assert mine == pytest.approx(float(
            aux["ce_per_token"] + MTP_LAMBDA * aux["ce_mtp_per_token"]),
            rel=1e-6)
        assert float(aux["ce_mtp_per_token"]) > 0
    else:
        assert float(aux["ce_per_token"]) == mine
        assert "ce_mtp_per_token" not in aux
    num = sum(float(jnp.sum((g_mine[p] - g_ref[p]) ** 2)) for p in g_ref)
    den = sum(float(jnp.sum(g_ref[p] ** 2)) for p in g_ref)
    assert math.sqrt(num / den) <= all_tol
    for p in g_ref:
        if p.endswith("router_bias"):
            # a selection has no gradient: exactly zero, in both
            assert not np.asarray(g_mine[p]).any()
            assert not np.asarray(g_ref[p]).any()
            continue
        gap = float(jnp.linalg.norm(g_mine[p] - g_ref[p])
                    / jnp.linalg.norm(g_ref[p]))
        assert gap <= leaf_tol, (p, gap)
    # rows of the embedding that the batch never names: exactly zero (with
    # the module, the targets name rows too)
    named = np.zeros(VOCAB, bool)
    named[np.unique(np.asarray(batch[0]))] = True
    if mtp:
        named[np.unique(np.asarray(batch[1]))] = True
    assert not np.asarray(g_mine["embed/embedding"])[~named].any()


def test_the_module_sends_gradient_to_embedding_and_head_by_a_second_path(
        batch):
    """With the module the head's gradient is the main path's plus lambda
    times the module's: it differs from the model without the module at
    the same weights, by the reference's own difference."""
    spec1, cfg1 = tiny(mtp=1)
    spec0, cfg0 = tiny(mtp=0)
    _, _, g1, _, r1 = _both_gradients(spec1, cfg1, batch)
    weights = seeded(cfg1)
    main = {p: v for p, v in weights.items() if not p.startswith("mtp_")}
    g0 = by_path(jax.grad(lambda p: make_loss_fn(spec0)(
        p, {}, batch, jax.random.PRNGKey(0))[0])(as_tree(main)))
    for leaf in ("lm_head", "embed/embedding"):
        second = g1[leaf] - g0[leaf]
        assert float(jnp.linalg.norm(second)) > 0.05 * float(
            jnp.linalg.norm(g0[leaf]))
        r0 = jax.grad(ref.loss)(main, (batch[0], batch[1], None), cfg0)[leaf]
        np.testing.assert_allclose(np.asarray(second),
                                   np.asarray(r1[leaf] - r0), atol=2e-6)


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

    assert err("float8") > 2 * err("float32")


def test_selection_follows_score_plus_bias_and_weights_the_scores_alone():
    rng = np.random.default_rng(2)
    scores = jax.nn.sigmoid(jnp.asarray(rng.normal(size=(64, 16)),
                                        jnp.float32))
    bias = jnp.asarray(0.5 * rng.normal(size=(16,)), jnp.float32)
    top, scale = 4, 2.5
    weights, order, inverse, sizes, served = moe.route(
        scores, top, 0, 16, scores + bias, scale)
    want = np.argsort(-np.asarray(scores + bias), axis=-1)[:, :top]
    plain = np.argsort(-np.asarray(scores), axis=-1)[:, :top]
    assert (np.sort(want, -1) != np.sort(plain, -1)).any()   # the bias bites
    # assignment a = token * top + slot went to expert `group[a]`
    group = np.empty(64 * top, np.int64)
    group[np.asarray(order)] = np.repeat(np.arange(16), np.asarray(sizes))
    np.testing.assert_array_equal(np.sort(group.reshape(64, top), -1),
                                  np.sort(want, -1))
    picked = np.take_along_axis(np.asarray(scores), group.reshape(64, top),
                                axis=-1)
    np.testing.assert_allclose(
        np.asarray(weights), scale * picked / picked.sum(-1, keepdims=True),
        rtol=1e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), scale, rtol=1e-6)
    assert bool(served.all())
    # and the reference's own gates, written apart, agree
    x = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
    chosen, gates = ref.gates(x, router, bias, {
        "num_experts_per_tok": top, "routed_scaling_factor": scale})
    mine = moe.route(jax.nn.sigmoid(x @ router), top, 0, 16,
                         jax.nn.sigmoid(x @ router) + bias, scale)
    group[np.asarray(mine[1])] = np.repeat(np.arange(16),
                                           np.asarray(mine[3]))
    np.testing.assert_array_equal(group.reshape(64, top), np.asarray(chosen))
    np.testing.assert_allclose(np.asarray(mine[0]), np.asarray(gates),
                               rtol=1e-6)


def test_adjacent_pair_rotary_against_the_direct_formula():
    d, theta = 8, 32e6
    inv = rope.rope_inv_freq(d, theta)
    x = np.random.default_rng(0).normal(size=(1, 6, 2, d)).astype(np.float32)
    got = np.asarray(rope.apply_rope(jnp.asarray(x), inv,
                                        interleave=True))
    for s in range(6):
        for j in range(d // 2):
            a, b = x[0, s, 1, 2 * j], x[0, s, 1, 2 * j + 1]
            c, sn = (math.cos(s * theta ** (-2 * j / d)),
                     math.sin(s * theta ** (-2 * j / d)))
            np.testing.assert_allclose(
                got[0, s, 1, 2 * j:2 * j + 2], [a * c - b * sn,
                                                b * c + a * sn], atol=1e-5)
    # the reference's own, written apart, agrees; and q k^T is what
    # de-interleaving both and turning the halves gives
    np.testing.assert_allclose(
        got, np.asarray(ref.rotate_adjacent(jnp.asarray(x), theta)),
        atol=1e-6)
    y = np.random.default_rng(1).normal(size=x.shape).astype(np.float32)
    halves = [np.asarray(rope.apply_rope(jnp.asarray(np.concatenate(
        [t[..., 0::2], t[..., 1::2]], -1)), inv)) for t in (x, y)]
    got_y = np.asarray(rope.apply_rope(jnp.asarray(y), inv,
                                          interleave=True))
    np.testing.assert_allclose((got * got_y).sum(-1),
                               (halves[0] * halves[1]).sum(-1), atol=1e-5)


@pytest.mark.parametrize("shape", [(2, 8192, 32, 192), (2, 8192, 1, 64)])
def test_the_cells_adjacent_pair_turn_is_one_pass_at_full_width(shape):
    """`joyai_mla_dp1`'s q, whole (its last 64 turned), and the one shared
    key part: no cosine per head, and nothing of the input's size is
    concatenated, split or reshaped to a minor axis of 2."""
    from test_mellum2 import assert_one_pass_turn
    assert_one_pass_turn(shape, 64, True)


def test_a_whole_head_turned_is_its_rotary_part_turned():
    """What `LatentAttention` asks for, the turn over a head of 16 + 8 with
    the scale and the one rounding inside, is what it used to put together:
    the first 16 in float32 beside the last 8 turned, times the scale,
    rounded; and the cotangent likewise."""
    rng = np.random.default_rng(3)
    inv = rope.rope_inv_freq(8, 32e6)
    x = jnp.asarray(rng.normal(size=(2, POSITIONS, 4, 24)), jnp.bfloat16)
    g = jnp.asarray(rng.normal(size=x.shape), jnp.bfloat16)

    def whole(x):
        return rope.apply_rope(x, inv, interleave=True,
                                  out_scale=24 ** -0.5, dtype=jnp.bfloat16)

    def parts(x):
        turned = rope.apply_rope(x[..., 16:], inv, interleave=True)
        return (jnp.concatenate([x[..., :16].astype(jnp.float32), turned],
                                axis=-1) * 24 ** -0.5).astype(jnp.bfloat16)

    (got, back), (want, want_back) = jax.vjp(whole, x), jax.vjp(parts, x)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    np.testing.assert_allclose(
        np.asarray(back(g)[0], np.float32),
        np.asarray(want_back(g)[0], np.float32), atol=2.0 ** -8)


def test_latent_attention_against_the_direct_formula():
    """One `[S, S]` softmax a head at S = 64, from the layer's own weights:
    rank bottlenecks with their norms, one rotary key for all heads, query
    and key heads of 16 + 8 against value heads of 16."""
    s, h, heads, nope, rot, dv, rank = 64, 64, 4, 16, 8, 16, 32
    spec, cfg = tiny(layers=1)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, s, h)), jnp.float32)
    w = {p[len("layers_0/attn/"):]: v for p, v in ref.init_params(
        jax.random.PRNGKey(1), cfg).items() if "layers_0/attn/" in p}
    w["q_a_norm/scale"] = jnp.asarray(rng.uniform(0.5, 1.5, 48), jnp.float32)
    w["kv_a_norm/scale"] = jnp.asarray(rng.uniform(0.5, 1.5, rank),
                                       jnp.float32)
    fields = dict(num_heads=heads, q_lora_rank=48, kv_lora_rank=rank,
                  qk_nope_head_dim=nope, qk_rope_head_dim=rot, v_head_dim=dv,
                  rope_theta=32e6, rope_interleave=True, rms_norm_eps=1e-6,
                  kernels=False, dtype=jnp.float32)
    got = joyai_flash.LatentAttention(types.SimpleNamespace(**fields)).apply(
        {"params": as_tree(w)}, x)

    def norm(v, scale):
        return v / np.sqrt((v * v).mean(-1, keepdims=True) + 1e-6) * scale

    def rope(v):        # [S, d]: pairs (2j, 2j + 1)
        out = np.array(v)
        for j in range(v.shape[-1] // 2):
            ang = np.arange(s) * 32e6 ** (-2 * j / v.shape[-1])
            a, b = v[:, 2 * j], v[:, 2 * j + 1]
            out[:, 2 * j] = a * np.cos(ang) - b * np.sin(ang)
            out[:, 2 * j + 1] = b * np.cos(ang) + a * np.sin(ang)
        return out

    w = {p: np.asarray(v, np.float64) for p, v in w.items()}
    want = np.zeros((2, s, h))
    for b in range(2):
        xb = np.asarray(x[b], np.float64)
        cq = norm(xb @ w["q_a_proj/kernel"], w["q_a_norm/scale"])
        kva = xb @ w["kv_a_proj/kernel"]
        ckv = norm(kva[:, :rank], w["kv_a_norm/scale"])
        k_rot = rope(kva[:, rank:])
        for i in range(heads):
            q = cq @ w["q_b_proj/kernel"][:, i]
            kv = ckv @ w["kv_b_proj/kernel"][:, i]
            q = np.concatenate([q[:, :nope], rope(q[:, nope:])], -1)
            k = np.concatenate([kv[:, :nope], k_rot], -1)
            scores = q @ k.T / math.sqrt(nope + rot)
            scores[np.triu_indices(s, 1)] = -np.inf
            p = np.exp(scores - scores.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            want[b] += (p @ kv[:, nope:]) @ w["o_proj/kernel"][i]
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


def test_blocked_attention_takes_values_of_their_own_head_size():
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(2, POSITIONS, 4, 1, 24)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, POSITIONS, 4, 24)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, POSITIONS, 4, 16)), jnp.float32)
    got = attention.plain_attention(q, k, v, None, block=8)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k)
    ok = attention.allowed(jnp.arange(POSITIONS), jnp.arange(POSITIONS), None)
    want = jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(
        jnp.where(ok, scores, -jnp.inf), axis=-1), v)
    assert got.shape == (2, POSITIONS, 4, 1, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def _layer(share, shares, experts, top):
    model = tiny(share, shares, experts=experts, top=top)[0].module
    return joyai_flash.Layer(common.own_fields(model), False)


@pytest.mark.parametrize("experts,top,shares", [
    (8, 2, 2), (16, 4, 4), (256, 8, 32)])
def test_the_shares_add_up(experts, top, shares):
    """Over all shares: the routed terms summed, with the attention and the
    shared expert (which every chip computes alike) counted once, equal
    the uncut reference's layer; the counters count every assignment
    once."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(2, POSITIONS, 64)), jnp.float32)
    held = experts // shares
    _, uncut = tiny(0, 1, experts=experts, top=top)
    weights = {p[len("expert_layers/"):]: v[0]
               for p, v in seeded(uncut).items()
               if p.startswith("expert_layers/")}
    for p in ("moe/w1", "moe/w3", "moe/w2"):    # terms large enough to see
        weights[p] = 5.0 * weights[p]
    want = ref.layer(x, weights, uncut, "float32", False)
    # what every share computes alike: the layer with no expert held
    nobody = dict(uncut, n_routed_experts=0)
    alike = ref.layer(x, {p: (v[:0] if p in ("moe/w1", "moe/w3", "moe/w2")
                              else v) for p, v in weights.items()},
                      nobody, "float32", False)
    routed, assigned = 0.0, 0.0
    for share in range(shares):
        mine = slice(share * held, (share + 1) * held)
        part = {p: (v[mine] if p in ("moe/w1", "moe/w3", "moe/w2") else v)
                for p, v in weights.items()}
        y, counters = _layer(share, shares, experts, top).apply(
            {"params": as_tree(part)}, x)
        routed = routed + (y - alike)
        assigned += float(counters["moe_held_assignments"])
    assert assigned == 2 * POSITIONS * top
    assert float(jnp.max(jnp.abs(want - alike))) > 0.05
    np.testing.assert_allclose(np.asarray(routed + alike), np.asarray(want),
                               atol=5e-5)


def test_no_token_is_dropped_when_every_token_goes_to_one_held_expert():
    """A selection bias that sends every token to expert 1 (and its second
    choice wherever the scores put it): expert 1 gets all T rows, more
    than twice an even load's, and the layer's output is the reference's."""
    experts, top, shares = 16, 2, 4
    rng = np.random.default_rng(13)
    x = jnp.asarray(rng.normal(size=(2, POSITIONS, 64)), jnp.float32)
    _, cfg = tiny(0, shares, experts=experts, top=top)
    weights = {p[len("expert_layers/"):]: v[0]
               for p, v in seeded(cfg).items()
               if p.startswith("expert_layers/")}
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
                                            False)), atol=5e-5)


def test_an_unknown_name_lists_the_known_ones():
    with pytest.raises(ValueError, match="joyai_flash"):
        get_model("joyai", "ptb")


def test_through_the_trainer_for_a_few_sparse_steps(tmp_path):
    """`--dnn joyai_flash --dataset ptb` builds through `make_trainer` like
    every other model, trains sparse steps on two workers under the
    default selector with the prediction module in the loss, and its
    `train` record carries the router's counters and the module's
    cross-entropy."""
    from gaussiank_sgd_tpu import train
    kw = {"hidden_size": 64, "num_layers": 3, "dense_width": 96,
          "num_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
          "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
          "num_experts": 8, "experts_per_token": 2, "expert_width": 32,
          "expert_share": 0, "expert_shares": 2,
          "num_nextn_predict_layers": 1, "seq_len": POSITIONS}
    data = {"vocab_size": VOCAB, "bptt": POSITIONS,
            "synthetic_tokens_n": 4 * (12 * POSITIONS + 1)}
    trainer = train.make_trainer([
        "--dnn", "joyai_flash", "--dataset", "ptb", "--nworkers", "2",
        "--batch-size", "2", "--compressor", "auto",
        "--density", "0.01",
        "--lr", "0.05", "--weight-decay", "0.0001", "--compute-dtype",
        "float32", "--max-steps", "8", "--log-every", "2",
        "--model-kwargs", json.dumps(kw), "--dataset-kwargs",
        json.dumps(data), "--output-dir", str(tmp_path)])
    try:
        assert trainer.spec.name == "joyai_flash"
        assert trainer._comp.name == "gaussian_fused"
        assert trainer.spec.mtp_lambda == 0.3
        first = trainer.train(2)
        rec = trainer.train(4)
    finally:
        trainer.close()
    assert np.isfinite(rec["loss"]) and rec["loss"] < first["loss"] + 0.5
    assert rec["num_selected"] > 0
    # 2 sequences x 32 positions x top-2 a worker in 2 expert layers and
    # the module's, half the experts held
    assert 0 < rec["moe_held_assignments"] <= 3 * 2 * POSITIONS * 2
    assert rec["moe_load_max_over_mean"] >= 1.0
    assert 0.0 <= rec["moe_tokens_unserved"] < 1.0
    assert rec["ce_mtp_per_token"] > 0
    assert rec["loss"] == pytest.approx(
        rec["ce_per_token"] + 0.3 * rec["ce_mtp_per_token"], rel=1e-4)
    with open(os.path.join(trainer.run_dir, "metrics.jsonl")) as f:
        trains = [r for r in map(json.loads, f) if r.get("event") == "train"]
    assert trains and all("ce_mtp_per_token" in r for r in trains)


def test_the_attention_kernels_lower_for_the_tpu_at_the_published_head_sizes():
    """Query and key heads of 192 against value heads of 128, a key/value
    head for every query head: forward and backward lower to Mosaic calls,
    the backward pass of these full layers to the dkv kernel alone, which
    computes dq too (checked without a chip, as
    `tests/test_kernel_lowering.py` does; the numbers are the chip's to
    prove, by the cell's `correct`)."""
    def loss(q, k, v):
        return jnp.sum(attention.splash_attention(q, k, v, None)
                       .astype(jnp.float32))

    s, heads = 1024, 4
    avals = [jax.ShapeDtypeStruct(shape, jnp.bfloat16) for shape in (
        (2, s, heads, 1, 192), (2, s, heads, 192), (2, s, heads, 128))]
    out = jax.eval_shape(attention.splash_attention, *avals, None)
    assert out.shape == (2, s, heads, 1, 128)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(*avals).lower(
        lowering_platforms=("tpu",)).as_text()
    # a full layer: the forward kernel and the ONE fused backward kernel
    assert text.count("tpu_custom_call") >= 2
    for kernel in ("splash_mha_fwd", "splash_mha_dkv"):
        assert kernel in text
    assert "splash_mha_dq" not in text
