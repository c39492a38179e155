"""`models/nemotron_h.py` and `models/blocks/ssm.py` at tiny widths on the CPU
(hidden 64; 4 state-space heads of 16 with a state of 16, `B` and `C` in 2
groups, a convolution of 4 taps with a bias, chunks of 8; 4 query and 2
key/value heads of 16 under no positions; 8 squared-ReLU experts of 32 top-2
behind a sigmoid router with a selection bias and a shared expert of 48; 44
positions, which is no whole number of the scan's chunks; five blocks of one
module each, `EMEM*`, an untied head), against the benchmark's plain
reference (`benchmarks/reference/nemotron_twotower_30b_a3b.py`, which imports
nothing of the program and runs the recurrence token by token) and against
direct formulas."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh

from benchmarks.reference import nemotron_twotower_30b_a3b as ref
from gaussiank_sgd_tpu import models
from gaussiank_sgd_tpu.compressors import get_compressor
from gaussiank_sgd_tpu.models import get_model, nemotron_h
from gaussiank_sgd_tpu.models.blocks import common, delta, ssm
from gaussiank_sgd_tpu.models.blocks import experts as moe
from gaussiank_sgd_tpu.parallel.bucketing import plan_for_params
from gaussiank_sgd_tpu.parallel.flat_opt import FlatSGDM
from gaussiank_sgd_tpu.parallel.trainstep import build_dp_train_step
from gaussiank_sgd_tpu.training.losses import make_loss_fn
from test_joyai_flash import as_tree, by_path, shapes_of

VOCAB, POSITIONS, PATTERN = 50, 44, "EMEM*"
CONFIG = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                      "configs", "nemotron_twotower_30b_a3b.json")
TINY = dict(hidden_size=64, pattern=PATTERN, mamba_num_heads=4,
            mamba_head_dim=16, ssm_state_size=16, n_groups=2, chunk_size=8,
            num_heads=4, num_kv_heads=2, head_dim=16, expert_width=32,
            shared_expert_width=48)


def tiny(share=0, shares=2, dtype=jnp.float32, experts=8, top=2,
         pattern=PATTERN):
    """(the program's model, the reference's configuration) of one share."""
    spec = get_model("nemotron_h", "ptb", vocab_size=VOCAB, dtype=dtype,
                     num_experts=experts, experts_per_token=top,
                     expert_share=share, expert_shares=shares,
                     **dict(TINY, pattern=pattern))
    cfg = {"hidden_size": 64, "num_hidden_layers": len(pattern),
           "hybrid_override_pattern": pattern, "mamba_num_heads": 4,
           "mamba_head_dim": 16, "ssm_state_size": 16, "n_groups": 2,
           "conv_kernel": 4, "use_conv_bias": True, "mamba_proj_bias": False,
           "layer_norm_epsilon": 1e-5, "time_step_min": 0.001,
           "time_step_max": 0.1, "time_step_floor": 1e-4, "head_dim": 16,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "moe_intermediate_size": 32,
           "moe_shared_expert_intermediate_size": 48,
           "n_routed_experts": experts // shares,
           "num_experts_per_tok": top, "norm_topk_prob": True,
           "routed_scaling_factor": 2.5, "n_group": 1, "topk_group": 1,
           "tie_word_embeddings": False, "vocab_size": VOCAB,
           "published": {"n_routed_experts": experts,
                         "num_hidden_layers": 52},
           "share": {"expert_share": share, "expert_shares": shares}}
    return spec, cfg


def seeded(cfg, key=7):
    """The reference's seeded weights with what starts at zero or one moved
    off it (the convolution's bias by a tenth and the router's selection
    bias by a fifth, drawn; the norms' scales and `D` by a tenth), so that
    each is seen to be read."""
    weights = ref.init_params(jax.random.PRNGKey(key), cfg)
    for i, p in enumerate(sorted(weights)):
        size = (0.2 if p.endswith("router_bias") else 0.1 if p.endswith(
            ("scale", "conv_bias", "/D")) else 0.0)
        if size:
            weights[p] = weights[p] + size * jax.random.normal(
                jax.random.PRNGKey(100 + i), weights[p].shape)
    return weights


def block_weights(cfg, index, key=7):
    return {p[len(f"blocks_{index}/"):]: v
            for p, v in seeded(cfg, key).items()
            if p.startswith(f"blocks_{index}/")}


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(3)
    toks = rng.integers(0, VOCAB, (2, POSITIONS + 1)).astype(np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


def test_parameter_paths_are_the_references():
    spec, cfg = tiny()
    assert "nemotron_h" in models.NAMES
    assert "nemotron_h" in models.TOKEN_MODELS
    assert spec.task == "lm" and spec.counters and spec.mtp_lambda == 0.0
    mine = shapes_of(spec, POSITIONS)
    assert mine == {p: tuple(s) for p, s in ref.param_shapes(cfg).items()}
    assert mine["lm_head"] == (64, VOCAB)
    for i in (1, 3):
        m = f"blocks_{i}/mixer/"
        # one matrix to [z | x B C | dt]: 64 + (64 + 2 * 2 * 16) + 4
        assert mine[m + "in_proj/kernel"] == (64, 64 + 128 + 4)
        assert mine[m + "conv_taps"] == (128, 4)
        assert mine[m + "conv_bias"] == (128,)
        assert mine[m + "A_log"] == mine[m + "dt_bias"] == mine[m + "D"] \
            == (4,)
        assert mine[m + "norm_scale"] == (64,)
        assert mine[m + "out_proj/kernel"] == (64, 64)
    for i in (0, 2):
        # two matrices an expert, routed and shared: no `w3`
        assert mine[f"blocks_{i}/moe/router"] == (64, 8)
        assert mine[f"blocks_{i}/moe/router_bias"] == (8,)
        assert mine[f"blocks_{i}/moe/w1"] == (4, 64, 32)
        assert mine[f"blocks_{i}/moe/w2"] == (4, 32, 64)
        assert mine[f"blocks_{i}/moe/shared/w1"] == (64, 48)
        assert not any(p.endswith("w3") for p in mine)
    assert mine["blocks_4/attn/q_proj/kernel"] == (64, 4, 16)
    assert not any("layernorm" in p or "gate_proj" in p for p in mine)
    # one norm a block, one module a block
    assert sum(p.endswith("norm/scale") for p in mine) == len(PATTERN) + 1


def test_published_widths_give_both_parameter_counts():
    """The benchmark's cut (blocks 6..12 of 52, 8 of 128 experts, 16 384
    rows of 131 072) at the published widths, from shapes alone; and the
    whole published causal tower, the 30 B of its name."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    assert cfg["arch"]["num_params"] == 528093120
    assert sum(math.prod(s) for s in ref.param_shapes(cfg).values()) \
        == 528093120 == (3 * 38744896 + 3 * 100125440 + 23399040
                         + 2 * 44040192 + 2688)
    kw = {k: v for k, v in cfg["trainer"]["model_kwargs"].items()
          if k != "seq_len"}
    spec = get_model("nemotron_h", "ptb", vocab_size=cfg["vocab_size"], **kw)
    assert shapes_of(spec, 128) == {
        p: tuple(s) for p, s in ref.param_shapes(cfg).items()}
    assert spec.module.pattern == ref.blocks(cfg) == "EMEMEM*" == "".join(
        cfg["published"]["hybrid_override_pattern"][i]
        for i in cfg["share"]["layers"])
    # every width is the published one, under the published config's keys
    m = spec.module
    assert (m.hidden_size, m.mamba_num_heads, m.mamba_head_dim,
            m.ssm_state_size, m.n_groups, m.conv_kernel, m.chunk_size,
            m.num_heads, m.num_kv_heads, m.head_dim, m.expert_width,
            m.shared_expert_width, m.num_experts, m.experts_per_token,
            m.route_scale, m.rms_norm_eps, ssm.DT_MIN, ssm.DT_MAX,
            ssm.DT_FLOOR, len(nemotron_h.PUBLISHED)) == (
        cfg["hidden_size"], cfg["mamba_num_heads"], cfg["mamba_head_dim"],
        cfg["ssm_state_size"], cfg["n_groups"], cfg["conv_kernel"],
        cfg["chunk_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"],
        cfg["moe_intermediate_size"],
        cfg["moe_shared_expert_intermediate_size"],
        cfg["published"]["n_routed_experts"], cfg["num_experts_per_tok"],
        cfg["routed_scaling_factor"], cfg["layer_norm_epsilon"],
        cfg["time_step_min"], cfg["time_step_max"], cfg["time_step_floor"],
        cfg["published"]["num_hidden_layers"]) == (
        2688, 64, 64, 128, 8, 4, 128, 32, 2, 128, 1856, 3712, 128, 6, 2.5,
        1e-5, 0.001, 0.1, 1e-4, 52)
    whole = dict(cfg, **{k: v for k, v in cfg["published"].items()
                         if k != "parameters"})
    whole["share"] = {"expert_share": 0, "expert_shares": 1}
    shapes = ref.param_shapes(whole)
    assert sum(math.prod(s) for s in shapes.values()) == 31577940288
    assert nemotron_h.PUBLISHED == whole["hybrid_override_pattern"]
    assert shapes_of(get_model("nemotron_h", "ptb"), 128) == {
        p: tuple(s) for p, s in shapes.items()}
    assert [nemotron_h.PUBLISHED.count(k) for k in "ME*"] == [23, 23, 6]
    per_block = {kind: sum(math.prod(s) for p, s in shapes.items()
                           if p.startswith(f"blocks_{i}/"))
                 for kind, i in (("M", 0), ("E", 1), ("*", 5))}
    assert per_block == {"M": 38744896, "E": 1297468160, "*": 23399040}


def test_the_models_own_initialiser_is_the_files():
    """Leaf by leaf the program's initialiser and the reference's seeded
    weights (the file's `assumed.init`) have one spread: 0.02 for a product,
    0.02 / sqrt(52) for each that WRITES the stream (a mixer's `out_proj`,
    the experts' and the shared expert's `w2`, the attention's `o_proj`), 1
    for the embedding; the leaves that are not drawn from a normal are equal
    or lie in one range."""
    spec, cfg = tiny(experts=16)
    mine = by_path(spec.module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, POSITIONS), jnp.int32),
        train=False)["params"])
    theirs = ref.init_params(jax.random.PRNGKey(7), cfg)
    assert set(mine) == set(theirs)
    out = 0.02 / math.sqrt(52)
    for path, _, how in ref._plan(cfg):
        a, b = np.asarray(mine[path]), np.asarray(theirs[path])
        if path.endswith(("out_proj/kernel", "o_proj/kernel", "w2")):
            assert how == pytest.approx(out), path
        elif isinstance(how, float) and how:
            assert how in (0.02, 1.0), path
        if isinstance(how, float) and how:
            assert a.std() == pytest.approx(how, rel=0.1), path
            assert b.std() == pytest.approx(how, rel=0.1), path
        elif how in ("uniform_taps", "dt_bias"):
            # the taps on (-1, 1) / sqrt(4); the bias between the inverse
            # softplus of 0.001 and of 0.1
            low, high = ((-0.5, 0.5) if how == "uniform_taps" else
                         (math.log(math.expm1(0.001)),
                          math.log(math.expm1(0.1))))
            for v in (a, b):
                assert low <= v.min() < v.max() <= high, path
        else:
            np.testing.assert_allclose(a, b, rtol=1e-6, err_msg=path)
    assert sum(path.endswith(("out_proj/kernel", "o_proj/kernel", "w2"))
               for path in mine) == 2 + 1 + 2 * 2


def _both_gradients(spec, cfg, batch, precision="float32"):
    weights = seeded(cfg)
    (mine, (_, aux)), g_mine = jax.value_and_grad(
        make_loss_fn(spec), has_aux=True)(
        as_tree(weights), {}, batch, jax.random.PRNGKey(0))
    theirs, g_ref = jax.value_and_grad(ref.loss)(
        weights, (batch[0], batch[1], None), cfg, precision)
    return float(mine), aux, by_path(g_mine), float(theirs), g_ref


# the decay's two leaves: 4 entries each that only the scan's state path
# reaches, a small part of the mixer's output beside `D x` at these widths
_DECAY_LEAVES = ("A_log", "dt_bias")


@pytest.mark.parametrize("dtype,loss_tol,all_tol,leaf_tol,decay_tol", [
    # float32 against float32, the chunked scan against the token-by-token
    # one
    (jnp.float32, 2e-6, 5e-6, 5e-5, 2e-3),
    # bfloat16 products against the float32 reference through five blocks
    (jnp.bfloat16, 1e-3, 0.012, 0.04, 0.2),
])
def test_loss_and_every_leafs_gradient_against_the_reference(
        batch, dtype, loss_tol, all_tol, leaf_tol, decay_tol):
    """Two state-space blocks, two expert blocks with half the experts, an
    attention block."""
    spec, cfg = tiny(dtype=dtype)
    mine, aux, g_mine, theirs, g_ref = _both_gradients(spec, cfg, batch)
    assert abs(mine - theirs) <= loss_tol * theirs
    assert float(aux["ce_per_token"]) == mine
    assert set(g_mine) == set(g_ref)
    num = sum(float(jnp.sum((g_mine[p] - g_ref[p]) ** 2)) for p in g_ref)
    den = sum(float(jnp.sum(g_ref[p] ** 2)) for p in g_ref)
    assert math.sqrt(num / den) <= all_tol
    for p in g_ref:
        if p.endswith("router_bias"):       # a selection has no gradient
            assert float(jnp.max(jnp.abs(g_mine[p]))) == 0.0 == float(
                jnp.max(jnp.abs(g_ref[p])))
            continue
        assert float(jnp.linalg.norm(g_ref[p])) > 0, p
        gap = float(jnp.linalg.norm(g_mine[p] - g_ref[p])
                    / jnp.linalg.norm(g_ref[p]))
        assert gap <= (decay_tol if p.endswith(_DECAY_LEAVES)
                       else leaf_tol), (p, gap)
    assert 0.0 < float(aux["ssm_dt_mean"]) < 1.0
    assert 0.0 < float(aux["ssm_decay_mean"]) < 1.0
    assert float(aux["ssm_state_rms"]) > 0.0
    assert float(aux["moe_held_assignments"]) > 0


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
    assert err("float8") > 0.012        # the bfloat16 test's limit


def test_one_sparse_steps_update_against_the_reference(batch):
    """The flat sparse step from the seeded weights, one worker: what it
    sent (read back from the parameters' change under momentum SGD's first
    step) plus what it kept as residual is the REFERENCE's gradient, entry
    by entry; what was sent is kept nowhere; about `k` entries were sent."""
    spec, cfg = tiny()
    weights = seeded(cfg)
    params = as_tree(weights)
    lr, decay, density = 0.05, 1e-4, 0.02
    plan = plan_for_params(params, density)
    ts = build_dp_train_step(
        make_loss_fn(spec), None, get_compressor("auto", density=density),
        plan, Mesh(np.array(jax.devices()[:1]), ("dp",)),
        flat_opt=FlatSGDM(lr=lr, momentum=0.9, weight_decay=decay))
    state = ts.init_state(params, jax.random.PRNGKey(2), model_state={},
                          carry=())
    after, metrics = ts.sparse_step(state, batch)
    before, _ = ravel_pytree(params)
    moved, _ = ravel_pytree(after.params)
    n = before.shape[0]
    sent = (before - moved) / lr - decay * before
    kept = after.ef_residual[:n]
    g_ref = jax.grad(ref.loss)(weights, (batch[0], batch[1], None), cfg)
    want, _ = ravel_pytree(as_tree(g_ref))
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(sent + kept), np.asarray(want),
                               atol=2e-4 * scale)
    chosen = np.asarray(kept) == 0.0
    np.testing.assert_allclose(np.asarray(sent)[~chosen], 0.0,
                               atol=2e-4 * scale)
    picked = int(np.sum(chosen & (np.abs(np.asarray(want)) > 0)))
    assert 0.3 * plan.total_k <= picked <= 3 * plan.total_k
    assert float(metrics.num_selected) > 0
    assert float(metrics.loss) == pytest.approx(float(ref.loss(
        weights, (batch[0], batch[1], None), cfg)), rel=1e-5)


def _scan_inputs(rate: float, key=1, b=2, t=50, g=2, h=4, p=6, n=5):
    """x, dt, a, B, C with `dt * a` about `-rate` a token."""
    ks = jax.random.split(jax.random.PRNGKey(key), 5)
    x = jax.random.normal(ks[0], (b, t, h, p))
    dt = rate * jax.random.uniform(ks[1], (b, t, h), minval=0.2, maxval=1.0)
    a = -jax.random.uniform(ks[2], (h,), minval=0.5, maxval=1.0)
    return (x, dt, a, jax.random.normal(ks[3], (b, t, g, n)),
            jax.random.normal(ks[4], (b, t, g, n)))


def _scan_gradients(scan, args):
    ks = jax.random.split(jax.random.PRNGKey(9), 2)

    def scalar(*a):
        y, state = scan(*a)
        return (jnp.sum(y * jax.random.normal(ks[0], y.shape))
                + jnp.sum(state * jax.random.normal(ks[1], state.shape)))

    return jax.grad(scalar, argnums=tuple(range(5)))(*args)


@pytest.mark.parametrize("chunk", [128, 25, 10, 7])
@pytest.mark.parametrize("rate", [1e-3, 3.0, 8.0, 40.0])
def test_the_chunked_scan_is_the_token_by_token_recurrence(chunk, rate):
    """Outputs, the state after the last token and every cotangent, at 50
    positions: one chunk that is not full, whole chunks (25, 10) and chunks
    with a rest (7); at decays that keep nearly everything, that forget
    within a chunk, within a token (the largest the published initialiser
    gives is 6.4 a token), and that would overflow any `exp(+sum)`. There a
    chunk's running sum reaches 2000, a difference of two such float32 sums
    is good to 1e-3, and the decay's own cotangent, a sum of a few terms of
    `exp(-8)` and less, shows it: held to 5 % (float64 says the recurrence
    is the one that is right, 1.4 % at one chunk of 50)."""
    args = _scan_inputs(rate)
    want, want_state = ssm.recurrent_scan(*args)
    got, state = ssm.chunked_scan(*args, chunk)
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5 * scale)
    np.testing.assert_allclose(np.asarray(state), np.asarray(want_state),
                               atol=2e-5 * float(jnp.max(jnp.abs(want_state))))
    g_want = _scan_gradients(ssm.recurrent_scan, args)
    g_got = _scan_gradients(
        lambda *a: ssm.chunked_scan(*a, chunk), args)
    for name, mine, theirs in zip(("x", "dt", "a", "B", "C"), g_got, g_want):
        assert np.all(np.isfinite(np.asarray(mine))), name
        tol = 0.05 if rate > 10 and name == "a" else 1e-4
        np.testing.assert_allclose(
            np.asarray(mine), np.asarray(theirs),
            atol=tol * float(jnp.max(jnp.abs(theirs))), err_msg=name)


def test_the_chunked_scan_with_bfloat16_products_stays_near():
    args = _scan_inputs(0.5, t=64)
    want, _ = ssm.recurrent_scan(*args)
    got, state = ssm.chunked_scan(*args, 16, jnp.bfloat16)
    assert got.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    err = float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                / jnp.linalg.norm(want))
    assert 0 < err < 0.02


def _conv_direct(x, taps, bias):
    """`silu(sum_j taps[:, j] x[t - 3 + j] + bias)` by a plain loop."""
    b, s, w = x.shape
    out = np.zeros((b, s, w), np.float64)
    for t in range(s):
        for j in range(taps.shape[1]):
            at = t - (taps.shape[1] - 1) + j
            if at >= 0:
                out[:, t] += np.asarray(taps)[:, j] * np.asarray(x)[:, at]
    out = out + (0.0 if bias is None else np.asarray(bias))
    return out / (1.0 + np.exp(-out))


@pytest.mark.parametrize("with_bias", [True, False])
def test_the_convolution_with_and_without_a_bias_against_a_plain_loop(
        with_bias):
    """`delta.conv_silu`, which the state-space mixer shares with the gated
    delta rule's: with the bias this mixer has and without, forward against
    a loop and backward against JAX's own transpose of the plain formula.
    The first positions see zeros before them, and the bias on every
    position."""
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    x = jax.random.normal(ks[0], (2, 9, 6))
    taps = jax.random.normal(ks[1], (6, 4))
    bias = jax.random.normal(ks[2], (6,)) if with_bias else None
    got = delta.conv_silu(x, taps, bias)
    np.testing.assert_allclose(np.asarray(got), _conv_direct(x, taps, bias),
                               atol=1e-5)
    if with_bias:
        first = np.asarray(taps)[:, 3] * np.asarray(x)[:, 0] + np.asarray(
            bias)
        np.testing.assert_allclose(np.asarray(got)[:, 0],
                                   first / (1 + np.exp(-first)), atol=1e-5)

    def plain(x, taps, bias):
        filled = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
        pre = sum(taps[:, j] * filled[:, j:j + 9] for j in range(4))
        return jax.nn.silu(pre if bias is None else pre + bias)

    seen = jax.random.normal(ks[3], got.shape)
    argnums = (0, 1, 2) if with_bias else (0, 1)
    mine = jax.grad(lambda *a: jnp.sum(delta.conv_silu(*a) * seen),
                    argnums=argnums)(x, taps, bias)
    theirs = jax.grad(lambda *a: jnp.sum(plain(*a) * seen),
                      argnums=argnums)(x, taps, bias)
    for a, b in zip(mine, theirs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_the_gated_group_norm_and_its_backward_pass():
    """Gate first, norm second, a GROUP's columns under one root mean
    square: `Zamba2RMSNormGated`'s order and extent, not
    `delta.normed_gate`'s."""
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    y, z = (jax.random.normal(k, (2, 7, 24)) for k in ks[:2])
    scale = 1.0 + 0.1 * jax.random.normal(ks[2], (24,))

    def plain(y, z, scale):
        v = (y * jax.nn.silu(z)).reshape(2, 7, 3, 8)
        v = v / jnp.sqrt(jnp.mean(v * v, axis=-1, keepdims=True) + 1e-5)
        return v.reshape(2, 7, 24) * scale

    got = ssm.gated_group_norm(y, z, scale, 3, 1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(plain(y, z, scale)),
                               atol=1e-5)
    # a run of columns is normed on its own: scaling another changes nothing
    other = ssm.gated_group_norm(y.at[..., 8:].multiply(3.0), z, scale, 3,
                                 1e-5)
    np.testing.assert_allclose(np.asarray(other)[..., :8],
                               np.asarray(got)[..., :8], atol=1e-5)
    assert float(jnp.max(jnp.abs(
        delta.normed_gate(y, z, scale, 1e-5) - got))) > 0.1
    seen = jax.random.normal(ks[3], got.shape)
    mine = jax.grad(lambda *a: jnp.sum(
        ssm.gated_group_norm(*a, 3, 1e-5) * seen), argnums=(0, 1, 2))(
        y, z, scale)
    theirs = jax.grad(lambda *a: jnp.sum(plain(*a) * seen),
                      argnums=(0, 1, 2))(y, z, scale)
    for a, b in zip(mine, theirs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_a_state_space_block_against_the_direct_recurrence():
    """One `M` block of the program against the reference's, which runs the
    recurrence token by token: the split `[z | x B C | dt]`, heads of a
    group sharing `B` and `C`, `D x`, the grouped norm."""
    rng = np.random.default_rng(12)
    x = jnp.asarray(rng.normal(size=(2, POSITIONS, 64)), jnp.float32)
    spec, cfg = tiny()
    weights = block_weights(cfg, 1)
    want = ref.block(x, weights, cfg, "float32", "M")
    got, counters = nemotron_h.Block(
        common.own_fields(spec.module), "M").apply(
        {"params": as_tree(weights)}, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert float(jnp.max(jnp.abs(want - x))) > 0.01
    assert set(counters) == {"ssm_dt_mean", "ssm_decay_mean", "ssm_state_rms"}


@pytest.mark.parametrize("form", [moe.RELU2, moe.GATED])
def test_an_experts_form_against_a_plain_loop(form):
    """All 4 experts held, top-2: each token's two experts by a loop over
    tokens, `W2 relu(W1 x)^2` with two matrices an expert and `W2 (silu(W1
    x) * W3 x)` with three; the shared expert in the same form; the
    gradient of every leaf the form has."""
    rng = np.random.default_rng(13)
    x = jnp.asarray(rng.normal(size=(1, 12, 16)), jnp.float32)
    layer = moe.Experts(4, 2, 8, 0, 1, jnp.float32, scoring="sigmoid",
                        scale=2.5, shared_width=12, form=form)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    params = jax.tree.map(lambda v: 10.0 * v, params)
    assert ("w3" in params) == (form == moe.GATED) == (
        "w3" in params["shared"])

    def one(w, v):
        up = v @ w["w1"]
        act = (jnp.square(jax.nn.relu(up)) if form == moe.RELU2
               else jax.nn.silu(up) * (v @ w["w3"]))
        return act @ w["w2"]

    chosen = np.argsort(-np.asarray(jax.nn.sigmoid(
        x.reshape(-1, 16) @ params["router"])), axis=-1)[:, :2]

    def plain(p):
        rows = x.reshape(-1, 16)
        scores = jax.nn.sigmoid(rows @ p["router"])
        out = []
        for t in range(rows.shape[0]):
            top = chosen[t]
            total = sum(scores[t, e] for e in top)
            y = one(p["shared"], rows[t])
            for e in top:
                y = y + 2.5 * scores[t, e] / total * one(
                    {k: p[k][e] for k in p if k in ("w1", "w2", "w3")},
                    rows[t])
            out.append(y)
        return jnp.stack(out).reshape(x.shape)

    got, counters = layer.apply({"params": params}, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(plain(params)),
                               atol=1e-5)
    assert float(counters["moe_held_assignments"]) == 24
    seen = jnp.asarray(rng.normal(size=x.shape), jnp.float32)
    mine = jax.grad(lambda p: jnp.sum(
        layer.apply({"params": p}, x)[0] * seen))(params)
    theirs = jax.grad(lambda p: jnp.sum(plain(p) * seen))(params)
    for p, v in by_path(theirs).items():
        np.testing.assert_allclose(np.asarray(by_path(mine)[p]),
                                   np.asarray(v), atol=2e-4, err_msg=p)


def test_an_unknown_form_is_refused():
    with pytest.raises(ValueError, match="form"):
        moe.Experts(4, 2, 8, 0, 1, jnp.float32, form="gelu").init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4, 16)))


@pytest.mark.parametrize("experts,top,shares", [(32, 4, 16), (8, 2, 2)])
def test_the_shares_add_up(experts, top, shares):
    """Over all shares (as the 16 shares of 8 of the cell's 128): the routed
    terms summed, with the shared expert (which every chip computes alike)
    counted once, equal the uncut reference's block; the counters count
    every assignment once."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(2, POSITIONS, 64)), jnp.float32)
    held = experts // shares
    _, uncut = tiny(0, 1, experts=experts, top=top)
    weights = block_weights(uncut, 0)
    routed_paths = ("moe/w1", "moe/w2")
    for p in routed_paths:                      # terms large enough to see
        weights[p] = 5.0 * weights[p]
    want = ref.block(x, weights, uncut, "float32", "E")
    # what every share computes alike: the block with no expert held
    alike = ref.block(x, {p: (v[:0] if p in routed_paths else v)
                          for p, v in weights.items()},
                      dict(uncut, n_routed_experts=0), "float32", "E")
    routed, assigned = 0.0, 0.0
    for share in range(shares):
        mine = slice(share * held, (share + 1) * held)
        part = {p: (v[mine] if p in routed_paths else v)
                for p, v in weights.items()}
        spec, cfg = tiny(share, shares, experts=experts, top=top)
        got = ref.block(x, part, cfg, "float32", "E")
        routed = routed + (got - alike)
        # the program's block is the reference's for this share, whole
        y, counters = nemotron_h.Block(
            common.own_fields(spec.module), "E").apply(
            {"params": as_tree(part)}, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(got), atol=5e-5)
        assigned += float(counters["moe_held_assignments"])
    assert assigned == 2 * POSITIONS * top
    assert float(jnp.max(jnp.abs(want - alike))) > 0.05
    np.testing.assert_allclose(np.asarray(routed + alike), np.asarray(want),
                               atol=5e-5)


def test_the_attention_block_knows_no_positions():
    """The block is the reference's, whose q and k are the plain
    projections: nothing turns them by their position."""
    rng = np.random.default_rng(14)
    x = jnp.asarray(rng.normal(size=(2, POSITIONS, 64)), jnp.float32)
    spec, cfg = tiny()
    weights = block_weights(cfg, 4)
    want = ref.block(x, weights, cfg, "float32", "*")
    got, counters = nemotron_h.Block(
        common.own_fields(spec.module), "*").apply(
        {"params": as_tree(weights)}, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert counters == {}


@pytest.fixture(scope="module")
def op_names():
    """The `op_name` of every instruction of the COMPILED sparse step at
    tiny widths (`tests/test_model_scopes.py` compiles it)."""
    from test_model_scopes import compiled_op_names
    return compiled_op_names("nemotron_h")


def test_the_scopes_the_cells_readers_take_are_on_the_compiled_step(op_names):
    from benchmarks import model_scopes, scope_tree, ssd_ops
    with open(CONFIG) as f:
        listed = json.load(f)["model_scopes"]
    by_scope = {}
    for name in op_names:
        scope = model_scopes.scope_of(name, listed)
        if scope:
            by_scope.setdefault(scope, []).append(name)
    assert set(ssd_ops.SCOPES) <= set(listed)
    # `ssm` has nothing of its own: all of the mixer is under one of the
    # five scopes inside it
    assert set(by_scope) | {"ssm"} == set(listed)
    for scope in ssd_ops.SCOPES[1:]:
        assert all("/ssm/" in n for n in by_scope[scope]), scope
        # the output product's recomputed forward is dead code: the
        # backward pass keeps its arguments, not its product
        assert {scope_tree.parse(n)[1] for n in by_scope[scope]} == set(
            scope_tree.PASSES) - ({"recomputed"} if scope == "ssm_out_proj"
                                  else set()), scope
    # a block is ONE module: the mixer on the M blocks, experts on the E
    # blocks, attention on the last, and nothing else anywhere
    for i, kind in enumerate("EMEM*"):
        mine = [n for n in op_names if f"/blocks_{i}/" in n]
        assert any("/ssm_scan/" in n for n in mine) == (kind == "M"), i
        assert any("/moe_experts/" in n for n in mine) == (kind == "E"), i
        assert any("/attn_full/" in n for n in mine) == (kind == "*"), i
    # no positions and no head norms: neither scope is opened
    assert not any("/rope/" in n or "/qk_norm/" in n for n in op_names)
    # the chunks' states and the groups are loops of the program
    assert any("/ssm_scan/" in n and "while" in n for n in op_names)
    # the experts' activation is the squared ReLU, under the gate's scope
    gate = {n.rsplit("/", 1)[-1] for n in op_names if "moe_gate" in n}
    assert {"max", "square"} <= gate and "logistic" not in gate
    norms = [n for n in op_names if "/rms_norm/" in n]
    assert all(any(f"/blocks_{i}/" in n for n in norms) for i in range(5))


def test_the_scans_kernels_carry_the_scans_scope_on_the_tpu_lowered_step():
    """Where the mixer takes `ops/ssd_scan.py`'s kernels (lowered for the
    TPU at a shape they take: chunks of 128, two heads of 64 a group, a
    state of 128), the sparse step's three calls each carry `ssm/ssm_scan`
    on their `op_name`, forward, recomputed and backward: `ssm_scan_ms`
    cannot silently empty into `no_scope_ms`."""
    import re
    positions = 256
    spec = get_model(
        "nemotron_h", "ptb", vocab_size=VOCAB, dtype=jnp.float32,
        seq_len=positions, kernels=True, **dict(
            TINY, pattern="MM", mamba_head_dim=64, ssm_state_size=128,
            chunk_size=128))
    tokens = jax.ShapeDtypeStruct((2, positions), jnp.int32)
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
    text = ts.sparse_step.trace(state, (tokens, tokens)).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    paths = re.findall(r'"([^"]*/ssd_\w+/pallas_call)"', text)
    by_kernel = {}
    for path in paths:
        by_kernel.setdefault(path.split("/")[-2], set()).add(path)
    # a block's forward pass and its recomputed one are both `ssd_fwd_kept`
    # (JAX evaluates a `custom_vjp`'s forward rule under `checkpoint` too)
    assert set(by_kernel) == {"ssd_fwd_kept", "ssd_bwd"}
    for kernel, mine in by_kernel.items():
        assert all("/ssm/ssm_scan/" in path for path in mine), kernel
        for block in ("blocks_0", "blocks_1"):
            assert any(f"/{block}/" in path for path in mine), (kernel, block)
    from benchmarks import scope_tree
    assert {scope_tree.parse(path)[1] for path in by_kernel["ssd_fwd_kept"]
            } == {"forward", "recomputed"}
    assert {scope_tree.parse(path)[1] for path in by_kernel["ssd_bwd"]
            } == {"backward"}


def test_an_unknown_pattern_is_refused():
    with pytest.raises(ValueError, match="nemotron_h"):
        get_model("nemotronh", "ptb")
    with pytest.raises(ValueError, match="pattern"):
        spec = get_model("nemotron_h", "ptb", vocab_size=VOCAB,
                         **dict(TINY, pattern="EM-"))
        spec.module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def test_through_the_trainer_for_a_few_sparse_steps(tmp_path):
    """`--dnn nemotron_h --dataset ptb` builds through `make_trainer` like
    every other model, trains sparse steps on two workers under the default
    selector with `A_log`, `dt_bias`, `D`, the taps and the convolution's
    bias in the one flat space, and its `train` record carries the routers'
    counters and the scan's."""
    from gaussiank_sgd_tpu import train
    kw = dict(TINY, num_experts=8, experts_per_token=2, expert_share=0,
              expert_shares=2, seq_len=POSITIONS)
    data = {"vocab_size": VOCAB, "bptt": POSITIONS,
            "synthetic_tokens_n": 4 * (12 * POSITIONS + 1)}
    trainer = train.make_trainer([
        "--dnn", "nemotron_h", "--dataset", "ptb", "--nworkers", "2",
        "--batch-size", "2", "--compressor", "auto", "--density", "0.01",
        "--lr", "0.005", "--weight-decay", "0.0001", "--compute-dtype",
        "float32", "--max-steps", "8", "--log-every", "2",
        "--model-kwargs", json.dumps(kw), "--dataset-kwargs",
        json.dumps(data), "--output-dir", str(tmp_path)])
    try:
        assert trainer.spec.name == "nemotron_h"
        assert trainer.spec.module.vocab_size == VOCAB
        assert trainer._comp.name == "gaussian_fused"
        first = trainer.train(2)
        rec = trainer.train(4)
    finally:
        trainer.close()
    assert np.isfinite(rec["loss"]) and rec["loss"] < first["loss"] + 0.5
    assert rec["num_selected"] > 0
    # 2 sequences x 44 positions x top-2 a worker in 2 expert blocks, half
    # the experts held
    assert 0 < rec["moe_held_assignments"] <= 2 * 2 * POSITIONS * 2
    assert rec["moe_load_max_over_mean"] >= 1.0
    assert 0.0 < rec["ssm_dt_mean"] < 1.0
    assert 0.0 < rec["ssm_decay_mean"] < 1.0 and rec["ssm_state_rms"] > 0.0
    with open(os.path.join(trainer.run_dir, "metrics.jsonl")) as f:
        trains = [r for r in map(json.loads, f) if r.get("event") == "train"]
    assert trains and all("ssm_state_rms" in r for r in trains)


def test_the_attention_kernels_lower_for_the_tpu_at_32_heads_over_2():
    """Heads of 128, sixteen query heads to each of two key/value heads,
    full, no positions: forward and backward lower to Mosaic calls (checked
    without a chip, as `tests/test_kernel_lowering.py` does; the numbers are
    the chip's to prove, by the cell's `correct`)."""
    from gaussiank_sgd_tpu.models.blocks import attention
    s, kv_heads, group = 1024, 2, 16
    avals = [jax.ShapeDtypeStruct(shape, jnp.bfloat16) for shape in (
        (2, s, kv_heads, group, 128), (2, s, kv_heads, 128),
        (2, s, kv_heads, 128))]

    def loss(q, k, v):
        return jnp.sum(attention.splash_attention(q, k, v, None)
                       .astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(*avals).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") >= 2


# sha256 of the tiny sparse step's lowered text (`tests/test_model_scopes.py`'s
# presets, one CPU device, Mosaic payloads blanked), computed on the PARENT of
# PR 48 (c68f1eb) from `git archive`, where `experts.py` knew one form and
# `delta.conv_silu` no bias
_PARENTS_STEPS = {
    "mellum2":
        "dc3f4b18c7294d123fd0add51beab03304f0204cd5269796829f6534d20f2ded",
    "joyai_flash":
        "b1f72f63f3ed627435af5f25d9d258f9fe73b408fbf101dc810c3e6ad997a0da",
    "lfm2_moe":
        "92d9524e67855b3ba8a78c77359ee49149552991dda51bef7281b8de5498e024",
    "afmoe":
        "f1c63f5e461ec5bfefd3a473d6deea75d3d3032ea344deb36b6d2f234f15ed6f",
    "qwen3_next":
        "6864c1a0f686c7d0a7daa9a8168cf085f8c370b52b76dfe6275bf1faa0ca68dd"}


@pytest.mark.parametrize("model", sorted(_PARENTS_STEPS))
def test_a_sibling_models_lowered_step_is_the_parents(model):
    """The experts' second form is a branch on a module field and the
    convolution's bias an argument that is None: `qwen3_next`'s lowered
    sparse step (the gated delta rule's mixer, which shares `conv_silu`)
    and the four expert siblings' are, text for text, what the parent
    lowered. A PR that MEANS to change one of these programs pins its own
    hash here and says so."""
    import hashlib
    import re
    from test_model_scopes import MODELS
    from test_model_scopes import POSITIONS as positions
    spec = get_model(model, "ptb", vocab_size=50, dtype=jnp.float32,
                     seq_len=positions, **MODELS[model])
    tokens = jax.ShapeDtypeStruct((2, positions), jnp.int32)
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
    text = ts.sparse_step.lower(state, (tokens, tokens)).as_text()
    text = re.sub(r'backend_config\s*=\s*"[^"]*"', 'backend_config=""', text)
    assert hashlib.sha256(text.encode()).hexdigest() == _PARENTS_STEPS[model]
