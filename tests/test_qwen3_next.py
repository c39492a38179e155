"""`models/qwen3_next.py` and `models/blocks/delta.py` at tiny widths on the
CPU (hidden 64; 2 key and 4 value heads of 16 under a convolution of 4 taps;
4 query and 2 key/value heads of 32 with the first 8 entries turned; 8
experts of 32 top-2 behind a softmax router with a gated shared expert; 40
positions, which is no whole number of the rule's chunks; four layers
`linear, linear, linear, full`, an untied head), against the benchmark's
plain reference (`benchmarks/reference/qwen3_next_80b_a3b.py`, which imports
nothing of the program and runs the rule token by token) and against direct
formulas."""

import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import qwen3_next_80b_a3b as ref
from gaussiank_sgd_tpu import models
from gaussiank_sgd_tpu.models import get_model, qwen3_next
from gaussiank_sgd_tpu.models.blocks import attention, common, delta, rope
from gaussiank_sgd_tpu.models.blocks import experts as moe
from gaussiank_sgd_tpu.training.losses import make_loss_fn
from test_joyai_flash import as_tree, by_path, shapes_of

VOCAB, POSITIONS = 50, 40
LINEAR, FULL = "linear_attention", "full_attention"
CONFIGS = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                       "configs")
CONFIG = os.path.join(CONFIGS, "qwen3_next_80b_a3b.json")
TINY = dict(hidden_size=64, num_layers=4, linear_num_key_heads=2,
            linear_num_value_heads=4, linear_key_head_dim=16,
            linear_value_head_dim=16, num_heads=4, num_kv_heads=2,
            head_dim=32, expert_width=32, shared_expert_width=32)


def tiny(share=0, shares=2, dtype=jnp.float32, experts=8, top=2):
    """(the program's model, the reference's configuration) of one share."""
    spec = get_model("qwen3_next", "ptb", vocab_size=VOCAB, dtype=dtype,
                     num_experts=experts, experts_per_token=top,
                     expert_share=share, expert_shares=shares, **TINY)
    cfg = {"hidden_size": 64, "num_hidden_layers": 4,
           "full_attention_interval": 4, "head_dim": 32,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "partial_rotary_factor": 0.25, "rope_theta": 10000000,
           "rope_scaling": None, "rms_norm_eps": 1e-6,
           "linear_conv_kernel_dim": 4, "linear_key_head_dim": 16,
           "linear_value_head_dim": 16, "linear_num_key_heads": 2,
           "linear_num_value_heads": 4, "moe_intermediate_size": 32,
           "shared_expert_intermediate_size": 32,
           "num_experts": experts // shares, "num_experts_per_tok": top,
           "norm_topk_prob": True, "tie_word_embeddings": False,
           "vocab_size": VOCAB, "published": {"num_experts": experts},
           "share": {"expert_share": share, "expert_shares": shares,
                     "layers": [0, 1, 2, 3]}}
    return spec, cfg


def seeded(cfg, key=7):
    """The reference's seeded weights with the zero-centred scales moved off
    zero (a tenth, drawn), so that `1 + w` is seen to be what multiplies."""
    weights = ref.init_params(jax.random.PRNGKey(key), cfg)
    for i, p in enumerate(sorted(weights)):
        if p.endswith("/scale") or p.endswith("layernorm"):
            weights[p] = weights[p] + 0.1 * jax.random.normal(
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
    assert "qwen3_next" in models.NAMES
    assert "qwen3_next" in models.TOKEN_MODELS
    assert spec.task == "lm" and spec.counters and spec.mtp_lambda == 0.0
    mine = shapes_of(spec, POSITIONS)
    assert mine == {p: tuple(s) for p, s in ref.param_shapes(cfg).items()}
    assert mine["lm_head"] == (64, VOCAB)
    for i in range(3):
        m = f"layers_{i}/mixer/linear_attn/"
        # one matrix to [q | k | v | z], a small one to [b | a]
        assert mine[m + "in_proj_qkvz/kernel"] == (64, 32 + 32 + 64 + 64)
        assert mine[m + "in_proj_ba/kernel"] == (64, 8)
        assert mine[m + "conv_taps"] == (128, 4)
        assert mine[m + "A_log"] == mine[m + "dt_bias"] == (4,)
        assert mine[m + "norm_scale"] == (16,)
    assert mine["layers_3/mixer/attn/gate_proj/kernel"] == (64, 4, 32)
    assert mine["layers_3/mixer/attn/q_layernorm"] == (32,)
    for i in range(4):
        assert mine[f"layers_{i}/routed/moe/router"] == (64, 8)
        assert mine[f"layers_{i}/routed/moe/w1"] == (4, 64, 32)
        assert mine[f"layers_{i}/routed/moe/shared_gate"] == (64,)


def test_published_widths_give_both_parameter_counts():
    """The benchmark's cut (layers 0-3 of 48, 16 of 512 experts, 18 992 rows
    of 151 936) at the published widths, from shapes alone; and the whole
    published model, the 80 B of its name."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    assert cfg["arch"]["num_params"] == 424340544
    assert sum(math.prod(s) for s in ref.param_shapes(cfg).values()) \
        == 424340544 == 3 * 88250560 + 81795584 + 2 * 38895616 + 2048
    kw = {k: v for k, v in cfg["trainer"]["model_kwargs"].items()
          if k != "seq_len"}
    spec = get_model("qwen3_next", "ptb", vocab_size=cfg["vocab_size"], **kw)
    assert shapes_of(spec, 128) == {
        p: tuple(s) for p, s in ref.param_shapes(cfg).items()}
    assert list(spec.module.layer_types) == ref.layers(cfg) == [
        cfg["layer_types"][i] for i in cfg["share"]["layers"]] == [
        LINEAR, LINEAR, LINEAR, FULL]
    # every width is the published one, under the published config's keys
    m = spec.module
    assert (m.hidden_size, m.num_heads, m.num_kv_heads, m.head_dim,
            m.partial_rotary_factor, m.rope_theta, m.rms_norm_eps,
            m.linear_num_key_heads, m.linear_num_value_heads,
            m.linear_key_head_dim, m.linear_value_head_dim,
            m.linear_conv_kernel_dim, m.expert_width, m.shared_expert_width,
            m.num_experts, m.experts_per_token) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"],
        cfg["partial_rotary_factor"], cfg["rope_theta"], cfg["rms_norm_eps"],
        cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
        cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
        cfg["linear_conv_kernel_dim"], cfg["moe_intermediate_size"],
        cfg["shared_expert_intermediate_size"],
        cfg["published"]["num_experts"], cfg["num_experts_per_tok"]) == (
        2048, 16, 2, 256, 0.25, 10000000, 1e-6, 16, 32, 128, 128, 4, 512,
        512, 512, 10)
    whole = dict(cfg, **cfg["published"])
    whole["share"] = {"expert_share": 0, "expert_shares": 1,
                      "layers": list(range(48))}
    shapes = ref.param_shapes(whole)
    assert sum(math.prod(s) for s in shapes.values()) == 79674391296
    assert shapes_of(get_model("qwen3_next", "ptb"), 128) == {
        p: tuple(s) for p, s in shapes.items()}
    mixer = {kind: sum(math.prod(s) for p, s in shapes.items()
                       if p.startswith(f"layers_{i}/mixer/")
                       and "input_norm" not in p)
             for kind, i in ((LINEAR, 0), (FULL, 3))}
    assert mixer == {LINEAR: 33718464, FULL: 27263488}


def _both_gradients(spec, cfg, batch, precision="float32"):
    weights = seeded(cfg)
    (mine, (_, aux)), g_mine = jax.value_and_grad(
        make_loss_fn(spec), has_aux=True)(
        as_tree(weights), {}, batch, jax.random.PRNGKey(0))
    theirs, g_ref = jax.value_and_grad(ref.loss)(
        weights, (batch[0], batch[1], None), cfg, precision)
    return float(mine), aux, by_path(g_mine), float(theirs), g_ref


# the decay's two leaves: 4 entries each whose gradient is a sum of terms
# that nearly cancel (6e-8 and 4e-8 in norm beside 2e-3 on the product that
# feeds them), so the order of the sums shows there first
_DECAY_LEAVES = ("A_log", "dt_bias")


@pytest.mark.parametrize("dtype,loss_tol,all_tol,leaf_tol,decay_tol", [
    # float32 against float32, the chunked rule against the token-by-token
    # one: the loss reads the same to 1e-7, all entries 6e-8 off, the worst
    # leaf (a small projection) 3e-6, the decay's leaves 4e-3
    (jnp.float32, 2e-6, 5e-6, 3e-5, 0.02),
    # bfloat16 products against the float32 reference through 4 layers: all
    # entries read 0.0040 off, the worst leaf 0.0115 (a norm's scale), the
    # decay's leaves 0.070; the float8 control reads 0.029 over all entries
    # and 0.32 on the decay's leaves, so the limits lie between
    (jnp.bfloat16, 1e-3, 0.01, 0.03, 0.2),
])
def test_loss_and_every_leafs_gradient_against_the_reference(
        batch, dtype, loss_tol, all_tol, leaf_tol, decay_tol):
    """Three linear layers, a full layer and half the experts."""
    spec, cfg = tiny(dtype=dtype)
    mine, aux, g_mine, theirs, g_ref = _both_gradients(spec, cfg, batch)
    assert abs(mine - theirs) <= loss_tol * theirs
    assert float(aux["ce_per_token"]) == mine
    assert set(g_mine) == set(g_ref)
    num = sum(float(jnp.sum((g_mine[p] - g_ref[p]) ** 2)) for p in g_ref)
    den = sum(float(jnp.sum(g_ref[p] ** 2)) for p in g_ref)
    assert math.sqrt(num / den) <= all_tol
    for p in g_ref:
        assert float(jnp.linalg.norm(g_ref[p])) > 0, p
        gap = float(jnp.linalg.norm(g_mine[p] - g_ref[p])
                    / jnp.linalg.norm(g_ref[p]))
        assert gap <= (decay_tol if p.endswith(_DECAY_LEAVES)
                       else leaf_tol), (p, gap)
    # both sigmoid gates at seeded weights sit in the middle of their range
    assert 0.45 < float(aux["attn_gate_mean"]) < 0.55
    assert 0.45 < float(aux["moe_shared_gate_mean"]) < 0.55
    assert 0.45 < float(aux["gdn_beta_mean"]) < 0.55
    assert 0.0 < float(aux["gdn_decay_mean"]) < 1.0
    assert float(aux["gdn_state_rms"]) > 0.0


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
    assert err("float8") > 0.01         # the bfloat16 test's limit


def _rule_inputs(low: float, key=1, b=2, t=50, hk=1, h=3, dk=8, dv=6):
    """q, k, v, g, beta with `g` uniform on (low, 0)."""
    ks = jax.random.split(jax.random.PRNGKey(key), 5)
    q = delta.l2_normed(jax.random.normal(ks[0], (b, t, hk, dk))) * dk ** -0.5
    k = delta.l2_normed(jax.random.normal(ks[1], (b, t, hk, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = low * jax.random.uniform(ks[3], (b, t, h))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return q, k, v, g, beta


def _rule_gradients(rule, args):
    """The cotangents of all five inputs under a loss that reads every
    output and the final state."""
    def loss(*a):
        o, state = rule(*a)
        return (jnp.sum(o * jnp.cos(jnp.arange(o.size)).reshape(o.shape))
                + jnp.sum(state * jnp.sin(jnp.arange(state.size)).reshape(
                    state.shape)))
    return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)


@pytest.mark.parametrize("chunk", [64, 16, 7, 25])
@pytest.mark.parametrize("low", [-1e-3, -3.0, -40.0])
def test_the_chunked_rule_is_the_token_by_token_rule(chunk, low):
    """Outputs, the final state and the cotangents of q, k, v, g and beta,
    at chunks that do (25) and do not (64, 16, 7) divide the 50 tokens, a
    chunk that is longer than the sequence (64), and decays near 1 (`g`
    above -0.001), in between and near 0 (`g` down to -40: `exp(G)` under a
    chunk's running sum underflows, and nothing overflows)."""
    args = _rule_inputs(low)
    o_want, s_want = delta.recurrent_rule(*args)
    o_got, s_got = delta.chunked_rule(*args, chunk=chunk)
    assert o_got.shape == o_want.shape == (2, 50, 3, 6)
    np.testing.assert_allclose(np.asarray(o_got), np.asarray(o_want),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(s_got), np.asarray(s_want),
                               atol=2e-5)
    want = _rule_gradients(delta.recurrent_rule, args)
    got = _rule_gradients(
        lambda *a: delta.chunked_rule(*a, chunk=chunk), args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert np.isfinite(np.asarray(a)).all(), name
        scale = float(jnp.max(jnp.abs(b)))
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-4 * scale, name


def test_the_chunked_rule_with_bfloat16_products_stays_near():
    args = _rule_inputs(-3.0, t=128)
    o_want, s_want = delta.recurrent_rule(*args)
    o_got, s_got = delta.chunked_rule(*args, chunk=64, dtype=jnp.bfloat16)
    assert o_got.dtype == jnp.bfloat16 and s_got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(o_got - o_want))) < 0.03 * float(
        jnp.max(jnp.abs(o_want)))
    assert float(jnp.max(jnp.abs(s_got - s_want))) < 0.03 * float(
        jnp.max(jnp.abs(s_want)))


@pytest.mark.parametrize("size,ones", [(7, False), (16, False), (33, False),
                                       (64, False), (64, True)])
def test_the_unit_lower_inverse_and_its_cotangent(size, ones):
    """Against numpy's inverse and the cotangent of `jnp.linalg.inv`; and on
    the matrix of ones below the diagonal, whose powers grow to 1e18 while
    its inverse is a 1 and a -1 a row: forward substitution forms none."""
    rng = np.random.default_rng(size)
    a = (np.ones((2, size, size)) if ones
         else rng.normal(size=(2, size, size)) / math.sqrt(size)
         ).astype(np.float32)
    lower = np.tril(a, -1)
    want = np.linalg.inv(np.eye(size) + lower.astype(np.float64))
    got = delta.unit_lower_inverse(jnp.asarray(a))
    np.testing.assert_allclose(np.asarray(got), want,
                               atol=1e-4 * np.abs(want).max())
    weights = jnp.asarray(rng.normal(size=want.shape), jnp.float32)
    mine = jax.grad(lambda a: jnp.sum(
        delta.unit_lower_inverse(a) * weights))(jnp.asarray(a))
    theirs = jax.grad(lambda a: jnp.sum(jnp.linalg.inv(
        jnp.eye(size) + jnp.tril(a, -1)) * weights))(jnp.asarray(a))
    np.testing.assert_allclose(np.asarray(mine), np.asarray(theirs),
                               atol=2e-4 * float(jnp.max(jnp.abs(theirs))))
    assert not np.asarray(mine)[:, np.triu_indices(size)[0],
                                np.triu_indices(size)[1]].any()


def test_the_convolutions_first_positions_see_zeros_before_them():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(2, 9, 6)), jnp.float32)
    taps = jnp.asarray(rng.normal(size=(6, 4)), jnp.float32)
    got = np.asarray(delta.conv_silu(x, taps))
    xs, w = np.asarray(x), np.asarray(taps)

    def silu(a):
        return a / (1.0 + np.exp(-a))

    # c_t = silu(sum_j w_j x_{t-3+j}): the first three see 1, 2 and 3 taps
    np.testing.assert_allclose(got[:, 0], silu(w[:, 3] * xs[:, 0]),
                               atol=1e-6)
    np.testing.assert_allclose(
        got[:, 1], silu(w[:, 2] * xs[:, 0] + w[:, 3] * xs[:, 1]), atol=1e-6)
    np.testing.assert_allclose(
        got[:, 2], silu(w[:, 1] * xs[:, 0] + w[:, 2] * xs[:, 1]
                        + w[:, 3] * xs[:, 2]), atol=1e-6)
    np.testing.assert_allclose(
        got[:, 5], silu(sum(w[:, j] * xs[:, 2 + j] for j in range(4))),
        atol=1e-6)
    # the backward pass that is written out is the forward's own
    plain = lambda x, taps: jnp.sum(jax.nn.silu(ref.causal_taps(x, taps))
                                    * jnp.cos(jnp.arange(x.size)).reshape(
                                        x.shape))
    mine = lambda x, taps: jnp.sum(delta.conv_silu(x, taps) * jnp.cos(
        jnp.arange(x.size)).reshape(x.shape))
    for a, b in zip(jax.grad(mine, (0, 1))(x, taps),
                    jax.grad(plain, (0, 1))(x, taps)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_the_normed_gate_and_its_backward_pass():
    rng = np.random.default_rng(6)
    o = jnp.asarray(rng.normal(size=(2, 5, 3, 16)), jnp.float32)
    z = jnp.asarray(rng.normal(size=(2, 5, 3, 16)), jnp.float32)
    scale = jnp.asarray(1.0 + 0.1 * rng.normal(size=(16,)), jnp.float32)

    def plain(o, z, scale):
        n = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-6)
        return n * scale * jax.nn.silu(z)

    np.testing.assert_allclose(
        np.asarray(delta.normed_gate(o, z, scale, 1e-6)),
        np.asarray(plain(o, z, scale)), atol=1e-6)
    pick = jnp.cos(jnp.arange(o.size)).reshape(o.shape)
    for a, b in zip(
            jax.grad(lambda *w: jnp.sum(delta.normed_gate(*w, 1e-6) * pick),
                     (0, 1, 2))(o, z, scale),
            jax.grad(lambda *w: jnp.sum(plain(*w) * pick), (0, 1, 2))(
                o, z, scale)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


@pytest.mark.parametrize("width,turned", [(256, 64), (32, 8)])
def test_the_partial_turn_takes_a_heads_first_entries(width, turned):
    """`apply_rope(lead=True)`: the first `turned` entries in pairs (i, i +
    turned / 2) by the direct formula, the rest untouched, forward and in
    the cotangent; without `lead` it is the LAST entries, as before."""
    rng = np.random.default_rng(width)
    x = jnp.asarray(rng.normal(size=(2, 12, 3, width)), jnp.float32)
    inv_freq = rope.rope_inv_freq(turned, 1e7)
    got = rope.apply_rope(x, inv_freq, lead=True)
    half = turned // 2
    ang = np.arange(12)[:, None] * np.asarray(inv_freq)[None, :]
    cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
    xs = np.asarray(x)
    a, c = xs[..., :half], xs[..., half:turned]
    np.testing.assert_allclose(np.asarray(got[..., :half]),
                               a * cos - c * sin, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got[..., half:turned]),
                               c * cos + a * sin, atol=1e-5)
    assert (np.asarray(got[..., turned:]) == xs[..., turned:]).all()
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.rotate_first(x, 1e7, turned)),
                               atol=1e-5)
    pick = jnp.cos(jnp.arange(x.size)).reshape(x.shape)
    np.testing.assert_allclose(
        np.asarray(jax.grad(lambda x: jnp.sum(rope.apply_rope(
            x, inv_freq, lead=True) * pick))(x)),
        np.asarray(jax.grad(lambda x: jnp.sum(ref.rotate_first(
            x, 1e7, turned) * pick))(x)), atol=1e-5)
    last = rope.apply_rope(x, inv_freq)
    assert (np.asarray(last[..., :width - turned])
            == xs[..., :width - turned]).all()
    assert not np.allclose(np.asarray(last[:, 1:, :, width - turned:]),
                           xs[:, 1:, :, width - turned:])


def test_the_zero_centred_norm_multiplies_by_one_plus_its_scale():
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(3, 5, 64)), jnp.float32)
    norm = common.RMSNorm(1e-6, jnp.float32, zero_centred=True)
    params = norm.init(jax.random.PRNGKey(0), x)
    # from zero: at seeded weights it is the plain norm at its unit scale
    assert not np.asarray(params["params"]["scale"]).any()
    plain = common.RMSNorm(1e-6, jnp.float32)
    unit = plain.init(jax.random.PRNGKey(0), x)
    assert (np.asarray(unit["params"]["scale"]) == 1.0).all()
    np.testing.assert_array_equal(np.asarray(norm.apply(params, x)),
                                  np.asarray(plain.apply(unit, x)))
    w = jnp.asarray(0.3 * rng.normal(size=(64,)), jnp.float32)
    want = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * (
        1.0 + w)
    np.testing.assert_allclose(
        np.asarray(norm.apply({"params": {"scale": w}}, x)),
        np.asarray(want), atol=1e-6)
    np.testing.assert_allclose(np.asarray(ref.rms_norm(x, w, 1e-6)),
                               np.asarray(want), atol=1e-6)
    # what the flat space's one decay rule does to it: `w <- w - lr wd w`
    # pulls w to 0, so the factor 1 + w to 1 and not to 0
    decayed = w * (1.0 - 0.1 * 0.5)
    assert float(jnp.max(jnp.abs(decayed))) < float(jnp.max(jnp.abs(w)))
    assert float(jnp.max(jnp.abs((1.0 + decayed) - 1.0))) < float(
        jnp.max(jnp.abs((1.0 + w) - 1.0)))


def test_the_shared_experts_gate_is_one_sigmoid_a_token():
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(2, 6, 64)), jnp.float32)
    _, cfg = tiny(0, 1)
    w = ref._under(layer_weights(cfg, 0), "routed/moe/")
    w["shared_gate"] = 10.0 * w["shared_gate"]       # off the middle
    layer = moe.Experts(8, 2, 32, 0, 1, jnp.float32, shared_width=32,
                        shared_gate=True)
    y, counters = layer.apply({"params": as_tree(w)}, x)
    flat = x.reshape(-1, 64)
    share = jax.nn.sigmoid(flat @ w["shared_gate"])
    shared = ref.gated(flat, w["shared/w1"], w["shared/w3"], w["shared/w2"],
                       "float32")
    ungated, plain = moe.Experts(
        8, 2, 32, 0, 1, jnp.float32, shared_width=32).apply(
        {"params": as_tree({p: v for p, v in w.items()
                            if p != "shared_gate"})}, x)
    np.testing.assert_allclose(
        np.asarray(y - ungated).reshape(-1, 64),
        np.asarray((share[:, None] - 1.0) * shared), atol=2e-6)
    assert float(jnp.std(share)) > 0.05
    np.testing.assert_allclose(float(counters["moe_shared_gate_mean"]),
                               float(jnp.mean(share)), rtol=1e-6)
    assert "moe_shared_gate_mean" not in plain
    np.testing.assert_allclose(
        np.asarray(y).reshape(-1, 64),
        np.asarray(ref.experts(flat, w, dict(cfg, num_experts=8),
                               "float32")), atol=2e-5)


@pytest.mark.parametrize("model,config,count,gated", [
    ("mellum2", "mellum2_12b_a2p5b", 340349184, False),
    ("lfm2_moe", "lfm2_8b_a1b", 507820288, False),
    ("joyai_flash", "joyai_llm_flash", 413959168, False),
    ("afmoe", "trinity_mini", 504147712, True)])
def test_the_accepted_models_keep_their_parameters(model, config, count,
                                                   gated):
    """`Attention(qk_norm_zero_centred=, rope_lead=)`, `RMSNorm(
    zero_centred=)`, `Experts(shared_gate=)` and `apply_rope(lead=)` default
    to what the four accepted models have: the same n at the benchmark's
    cut under the same paths as their references give, every norm's scale
    from one. Their compiled steps' temporaries, device-less for a v5e,
    parent against change: PERF.md section 6, PR 44."""
    import importlib
    with open(os.path.join(CONFIGS, config + ".json")) as f:
        cfg = json.load(f)
    kw = {k: v for k, v in cfg["trainer"]["model_kwargs"].items()
          if k != "seq_len"}
    spec = get_model(model, "ptb", vocab_size=cfg["vocab_size"], **kw)
    shapes = shapes_of(spec, 128)
    assert sum(math.prod(s) for s in shapes.values()) == count \
        == cfg["arch"]["num_params"]
    theirs = importlib.import_module(
        "benchmarks.reference." + cfg["reference"]).param_shapes(cfg)
    assert shapes == {p: tuple(s) for p, s in theirs.items()}
    assert not any("shared_gate" in p or "linear_attn" in p for p in shapes)
    assert any("gate_proj" in p for p in shapes) == gated
    from test_model_scopes import MODELS
    tiny_spec = get_model(model, "ptb", vocab_size=VOCAB, **MODELS[model])
    params = by_path(tiny_spec.module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 32), jnp.int32),
        train=False)["params"])
    scales = [p for p in params if p.endswith("/scale")
              or p.endswith("layernorm")]
    assert scales and all((np.asarray(params[p]) == 1.0).all()
                          for p in scales)


@pytest.mark.parametrize("model,hardware", [
    ("qwen3_next", True), ("mellum2", False), ("lfm2_moe", False),
    ("joyai_flash", False), ("afmoe", False)])
def test_which_generator_draws_a_models_weights(model, hardware):
    """`Qwen3Next.init` hands flax its keys as the hardware generator's, so
    its leaves are drawn by `rng_bit_generator`; the four accepted models'
    init programs have none (the default generator's, as on the parent)."""
    from test_model_scopes import MODELS
    if model == "qwen3_next":
        spec = tiny()[0]
    else:
        spec = get_model(model, "ptb", vocab_size=VOCAB, **MODELS[model])
    tokens = jnp.zeros((1, 32), jnp.int32)
    program = jax.jit(lambda key: spec.module.init(
        {"params": key, "dropout": key}, tokens, train=False)).lower(
            jax.random.PRNGKey(0)).as_text()
    assert ("rng_bit_generator" in program) == hardware
    if not hardware:
        return
    shapes = shapes_of(spec, 32)
    a, b = (by_path(spec.module.init(jax.random.PRNGKey(k), tokens,
                                     train=False)["params"]) for k in (0, 1))
    assert all(a[p].shape == shapes[p] for p in shapes)
    assert not np.array_equal(a["lm_head"], b["lm_head"])
    assert abs(float(np.std(a["lm_head"])) - 0.02) < 0.002


def _layer(share, shares, experts, top, kind):
    model = tiny(share, shares, experts=experts, top=top)[0].module
    return qwen3_next.Layer(common.own_fields(model), kind)


@pytest.mark.parametrize("experts,top,shares,kind", [
    (32, 4, 8, LINEAR), (32, 4, 8, FULL), (8, 2, 2, LINEAR)])
def test_the_shares_add_up(experts, top, shares, kind):
    """Over all shares (as the 32 shares of 16 of the cell's 512): the
    routed terms summed, with the mixer, the shared expert and its gate
    (which every chip computes alike) counted once, equal the uncut
    reference's layer; the counters count every assignment once."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(2, POSITIONS, 64)), jnp.float32)
    held = experts // shares
    index = 3 if kind == FULL else 1
    _, uncut = tiny(0, 1, experts=experts, top=top)
    weights = layer_weights(uncut, index)
    routed_paths = tuple(f"routed/moe/{w}" for w in ("w1", "w3", "w2"))
    for p in routed_paths:                      # terms large enough to see
        weights[p] = 5.0 * weights[p]
    want = ref.layer(x, weights, uncut, "float32", kind)
    # what every share computes alike: the layer with no expert held
    alike = ref.layer(x, {p: (v[:0] if p in routed_paths else v)
                          for p, v in weights.items()},
                      dict(uncut, num_experts=0), "float32", kind)
    routed, assigned = 0.0, 0.0
    for share in range(shares):
        mine = slice(share * held, (share + 1) * held)
        part = {p: (v[mine] if p in routed_paths else v)
                for p, v in weights.items()}
        _, cfg = tiny(share, shares, experts=experts, top=top)
        got = ref.layer(x, part, cfg, "float32", kind)
        routed = routed + (got - alike)
        # the program's layer is the reference's for this share, whole
        y, (counters, _) = _layer(share, shares, experts, top, kind).apply(
            {"params": as_tree(part)}, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(got), atol=5e-5)
        assigned += float(counters["moe_held_assignments"])
    assert assigned == 2 * POSITIONS * top
    assert float(jnp.max(jnp.abs(want - alike))) > 0.05
    np.testing.assert_allclose(np.asarray(routed + alike), np.asarray(want),
                               atol=5e-5)


@pytest.fixture(scope="module")
def op_names():
    """The `op_name` of every instruction of the COMPILED sparse step at
    tiny widths (`tests/test_model_scopes.py` compiles it)."""
    from test_model_scopes import compiled_op_names
    return compiled_op_names("qwen3_next")


@pytest.fixture(scope="module")
def kernel_paths():
    """The name stack of every Mosaic call of one linear layer's gradient,
    lowered for the TPU at heads of 128 and one chunk of positions with
    `kernels` (the CPU's compiled step above runs `chunked_rule`)."""
    from test_model_scopes import MODELS
    spec = get_model("qwen3_next", "ptb", vocab_size=VOCAB, seq_len=64, **{
        **MODELS["qwen3_next"], "num_layers": 1, "linear_num_key_heads": 1,
        "linear_num_value_heads": 2, "linear_key_head_dim": 128,
        "linear_value_head_dim": 128, "kernels": True})
    tokens = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    params = jax.eval_shape(
        lambda t: spec.module.init({"params": jax.random.PRNGKey(0)}, t,
                                   train=False), tokens)["params"]
    def loss(p, t):
        with jax.named_scope("fwd_bwd"):        # as the step names it
            return jnp.sum(spec.module.apply({"params": p}, t))
    text = jax.jit(jax.grad(loss)).trace(params, tokens).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    return re.findall(r'"([^"]*/pallas_call)"', text)


def test_the_scopes_the_cells_readers_take_are_on_the_compiled_step(
        op_names, kernel_paths):
    from benchmarks import gdn_ops, model_scopes, scope_tree
    # the mixer's kernels carry the whole path, so `gdn_rule_ms` and
    # `gdn_conv_ms` read them by `op_name` like any fusion: both forward
    # passes are the prologue's forward kernel and the rule's that keeps the
    # states (`jax.checkpoint` runs the forward RULE in the first too), the
    # backward pass is one kernel of each
    mine = [n for n in kernel_paths if "/gdn_" in n]
    with open(CONFIG) as f:
        listed = json.load(f)["model_scopes"]
    for n in mine:
        under = re.search(
            r"/linear_attn/(gdn_rule|gdn_conv)/gdn_\w+/pallas_call$", n)
        assert under and model_scopes.scope_of(n, listed) == under.group(1), n
    assert sorted((scope_tree.parse(n)[1], n.split("/")[-3], n.split("/")[-2])
                  for n in mine) == [
        ("backward", "gdn_conv", "gdn_conv_bwd"),
        ("backward", "gdn_rule", "gdn_bwd"),
        ("forward", "gdn_conv", "gdn_conv_fwd"),
        ("forward", "gdn_rule", "gdn_fwd_kept"),
        ("recomputed", "gdn_conv", "gdn_conv_fwd"),
        ("recomputed", "gdn_rule", "gdn_fwd_kept")]
    by_scope = {}
    for name in op_names:
        scope = model_scopes.scope_of(name, listed)
        if scope:
            by_scope.setdefault(scope, []).append(name)
    assert set(gdn_ops.SCOPES) <= set(listed)
    # `linear_attn` has nothing of its own: all of the mixer is under one of
    # the five scopes inside it
    assert set(by_scope) | {"linear_attn"} == set(listed)
    for scope in gdn_ops.SCOPES[1:]:
        assert all("/linear_attn/" in n for n in by_scope[scope]), scope
        # the output product's recomputed forward is dead code: the
        # backward pass keeps its arguments, not its product
        assert {scope_tree.parse(n)[1] for n in by_scope[scope]} == set(
            scope_tree.PASSES) - ({"recomputed"} if scope == "gdn_out_proj"
                                  else set()), scope
    # the mixer is on the three linear layers and not on the full one, whose
    # `attn_proj` holds the head norms, the turn and the gate
    for i, there in enumerate((True, True, True, False)):
        mine = [n for n in op_names if f"/layers_{i}/" in n]
        assert any("/gdn_rule/" in n for n in mine) == there
        assert any("/attn_full/" in n for n in mine) != there
    full = [n for n in op_names if "/layers_3/" in n]
    for scope in ("/attn_proj/qk_norm/", "/attn_proj/rope/",
                  "/attn_proj/attn_gate/"):
        assert any(scope in n for n in full), scope
    # the rule's scan is a loop of the program, not of its text
    assert any("/gdn_rule/" in n and "while" in n for n in op_names)
    # the shared expert's gate (the one sum over a token's entries that
    # the shared expert has) is under the shared expert's scope
    assert any("/moe_shared/" in n and n.endswith("reduce_sum")
               for n in op_names)
    norms = [n for n in op_names if "/rms_norm/" in n]
    assert {m for n in norms for m in ("input_norm", "post_attn_norm")
            if f"/{m}/" in n} == {"input_norm", "post_attn_norm"}


def test_an_unknown_layer_type_is_refused():
    with pytest.raises(ValueError, match="qwen3_next"):
        get_model("qwen3next", "ptb")
    with pytest.raises(ValueError, match="layer_types"):
        spec = get_model("qwen3_next", "ptb", vocab_size=VOCAB, **{
            **TINY, "num_layers": 2,
            "layer_types": ["conv", "linear_attention"]})
        spec.module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def test_through_the_trainer_for_a_few_sparse_steps(tmp_path):
    """`--dnn qwen3_next --dataset ptb` builds through `make_trainer` like
    every other model, trains sparse steps on two workers under the default
    selector with the decay's, the taps' and the zero-centred scales' leaves
    in the one flat space, and its `train` record carries the routers'
    counters, both gates' means and the rule's."""
    from gaussiank_sgd_tpu import train
    kw = dict(TINY, num_experts=8, experts_per_token=2, expert_share=0,
              expert_shares=2, seq_len=POSITIONS)
    data = {"vocab_size": VOCAB, "bptt": POSITIONS,
            "synthetic_tokens_n": 4 * (12 * POSITIONS + 1)}
    trainer = train.make_trainer([
        "--dnn", "qwen3_next", "--dataset", "ptb", "--nworkers", "2",
        "--batch-size", "2", "--compressor", "auto", "--density", "0.01",
        "--lr", "0.005", "--weight-decay", "0.0001", "--compute-dtype",
        "float32", "--max-steps", "8", "--log-every", "2",
        "--model-kwargs", json.dumps(kw), "--dataset-kwargs",
        json.dumps(data), "--output-dir", str(tmp_path)])
    try:
        assert trainer.spec.name == "qwen3_next"
        assert trainer.spec.module.vocab_size == VOCAB
        assert trainer._comp.name == "gaussian_fused"
        first = trainer.train(2)
        rec = trainer.train(4)
    finally:
        trainer.close()
    assert np.isfinite(rec["loss"]) and rec["loss"] < first["loss"] + 0.5
    assert rec["num_selected"] > 0
    # 2 sequences x 40 positions x top-2 a worker in 4 expert layers, half
    # the experts held
    assert 0 < rec["moe_held_assignments"] <= 4 * 2 * POSITIONS * 2
    assert rec["moe_load_max_over_mean"] >= 1.0
    for gate in ("attn_gate_mean", "moe_shared_gate_mean", "gdn_beta_mean"):
        assert 0.4 < rec[gate] < 0.6, gate
    assert 0.0 < rec["gdn_decay_mean"] < 1.0 and rec["gdn_state_rms"] > 0.0
    with open(os.path.join(trainer.run_dir, "metrics.jsonl")) as f:
        trains = [r for r in map(json.loads, f) if r.get("event") == "train"]
    assert trains and all("gdn_state_rms" in r for r in trains)


def test_the_attention_kernels_lower_for_the_tpu_at_heads_of_256():
    """Heads of 256, eight query heads to each of two key/value heads, full:
    forward and backward lower to Mosaic calls, the backward pass to the ONE
    fused kernel on compute tiles of half as many keys (a head wider than
    128 lanes; checked without a chip, as `tests/test_kernel_lowering.py`
    does; the numbers are the chip's to prove, by the cell's `correct`)."""
    s, kv_heads, group = 1024, 2, 8
    avals = [jax.ShapeDtypeStruct(shape, jnp.bfloat16) for shape in (
        (2, s, kv_heads, group, 256), (2, s, kv_heads, 256),
        (2, s, kv_heads, 256))]

    def loss(q, k, v):
        return jnp.sum(attention.splash_attention(q, k, v, None)
                       .astype(jnp.float32))
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
        *avals).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 2
    sizes = attention.splash_sizes(2, 8192, 16, 256, None)
    assert sizes.use_fused_bwd_kernel
    assert (sizes.block_kv_dkv_compute, sizes.block_kv_compute) == (256, 512)
