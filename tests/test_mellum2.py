"""`models/mellum2.py` at tiny widths on the CPU (hidden 64, 4 query and 2
key/value heads of 16, 8 experts top-2, window 8, 32 positions, 4 layers
`s, s, s, f`), against the benchmark's plain reference
(`benchmarks/reference/mellum2_12b_a2p5b.py`, which imports nothing of the
program) and against direct formulas."""

import contextlib
import functools
import json
import math
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import mellum2_12b_a2p5b as ref
from gaussiank_sgd_tpu.models import get_model
from gaussiank_sgd_tpu.models.blocks import attention, rope
from gaussiank_sgd_tpu.models.blocks import experts as moe
from gaussiank_sgd_tpu.training.losses import make_loss_fn

VOCAB, POSITIONS, WINDOW = 50, 32, 8
KINDS = ["sliding_attention"] * 3 + ["full_attention"]
ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 16, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}


def tiny(share=0, shares=2, dtype=jnp.float32, **kw):
    """(the program's model, the reference's configuration) of one share."""
    kw = dict(dict(
        hidden_size=64, num_layers=4, layer_types=KINDS, num_heads=4,
        num_kv_heads=2, head_dim=16, sliding_window=WINDOW, num_experts=8,
        experts_per_token=2, expert_width=32, expert_share=share,
        expert_shares=shares, yarn_original_max=16), **kw)
    spec = get_model("mellum2", "ptb", vocab_size=VOCAB, dtype=dtype, **kw)
    cfg = {"hidden_size": 64, "num_hidden_layers": kw["num_layers"],
           "layer_types": KINDS, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 16, "sliding_window": WINDOW,
           "num_experts": 8 // shares, "num_experts_per_tok": 2,
           "moe_intermediate_size": 32, "vocab_size": VOCAB,
           "rms_norm_eps": 1e-6, "rope_parameters": ROPE,
           "published": {"num_experts": 8},
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


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(3)
    toks = rng.integers(0, VOCAB, (2, POSITIONS + 1)).astype(np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


def test_parameter_paths_and_count_are_the_references():
    spec, cfg = tiny()
    shapes = jax.eval_shape(
        lambda x: spec.module.init(jax.random.PRNGKey(0), x, train=False),
        jnp.zeros((2, POSITIONS), jnp.int32))["params"]
    mine = {p: tuple(v.shape) for p, v in by_path(shapes).items()}
    assert mine == {p: tuple(s) for p, s in ref.param_shapes(cfg).items()}


def test_published_widths_give_the_cells_parameter_count():
    """The benchmark's cut (4 layers, 8 of 64 experts, 12 288 rows) at the
    published widths: 340 349 184 parameters, from shapes alone."""
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                           "configs", "mellum2_12b_a2p5b.json")) as f:
        cfg = json.load(f)
    n = sum(math.prod(s) for s in ref.param_shapes(cfg).values())
    assert n == cfg["arch"]["num_params"] == 340349184
    kw = cfg["trainer"]["model_kwargs"]
    spec = get_model("mellum2", "ptb", vocab_size=cfg["vocab_size"],
                     **{k: v for k, v in kw.items() if k != "seq_len"})
    shapes = jax.eval_shape(
        lambda x: spec.module.init(jax.random.PRNGKey(0), x, train=False),
        jnp.zeros((1, 128), jnp.int32))["params"]
    assert ({p: tuple(v.shape) for p, v in by_path(shapes).items()}
            == {p: tuple(s) for p, s in ref.param_shapes(cfg).items()})
    # every width is the published one
    m = spec.module
    assert (m.hidden_size, m.num_heads, m.num_kv_heads, m.head_dim,
            m.expert_width, m.num_experts, m.experts_per_token,
            m.sliding_window) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"],
        cfg["moe_intermediate_size"], cfg["published"]["num_experts"],
        cfg["num_experts_per_tok"], cfg["sliding_window"]) == (
        2304, 32, 4, 128, 896, 64, 8, 1024)
    yarn = cfg["rope_parameters"]["full_attention"]
    assert (m.rope_theta, m.yarn_factor, m.yarn_original_max,
            m.yarn_beta_fast, m.yarn_beta_slow, m.yarn_attention_factor) == (
        yarn["rope_theta"], yarn["factor"],
        yarn["original_max_position_embeddings"], yarn["beta_fast"],
        yarn["beta_slow"], yarn["attention_factor"])


@pytest.mark.parametrize("dtype,precision,loss_tol,grad_tol", [
    # float32 against float32, reduction order only: over three seeds the
    # loss reads at most 1.3e-7 off, the gradient 5.5e-8
    (jnp.float32, "float32", 2e-6, 2e-6),
    # bfloat16 products against the float32 reference, 8 bits of mantissa
    # through 4 layers: over three seeds the loss reads at most 3.5e-5 off,
    # the gradient and the head's 0.0046; the float8 control reads 0.029
    # at least, so the limit lies between
    (jnp.bfloat16, "float32", 5e-4, 0.012),
])
def test_loss_and_gradients_against_the_reference(batch, dtype, precision,
                                                  loss_tol, grad_tol):
    spec, cfg = tiny(dtype=dtype)
    weights = ref.init_params(jax.random.PRNGKey(7), cfg)
    params = as_tree(weights)
    loss_fn = make_loss_fn(spec)
    (mine, (_, aux)), g_mine = jax.value_and_grad(loss_fn, has_aux=True)(
        params, {}, batch, jax.random.PRNGKey(0))
    theirs, g_ref = jax.value_and_grad(ref.loss)(
        weights, (batch[0], batch[1], None), cfg, precision)
    assert abs(float(mine) - float(theirs)) <= loss_tol * float(theirs)
    assert float(aux["ce_per_token"]) == float(mine)
    g_mine = by_path(g_mine)
    num = sum(float(jnp.sum((g_mine[p] - g_ref[p]) ** 2)) for p in g_ref)
    den = sum(float(jnp.sum(g_ref[p] ** 2)) for p in g_ref)
    assert math.sqrt(num / den) <= grad_tol
    head = float(jnp.linalg.norm(g_mine["lm_head"] - g_ref["lm_head"])
                 / jnp.linalg.norm(g_ref["lm_head"]))
    assert head <= grad_tol
    # rows of the embedding that the batch never names: exactly zero
    named = np.zeros(VOCAB, bool)
    named[np.unique(np.asarray(batch[0]))] = True
    assert not np.asarray(g_mine["embed/embedding"])[~named].any()


def test_the_float8_control_is_further_from_the_program_than_float32(batch):
    spec, cfg = tiny(dtype=jnp.bfloat16)
    weights = ref.init_params(jax.random.PRNGKey(7), cfg)
    g_mine = by_path(jax.grad(lambda p: make_loss_fn(spec)(
        p, {}, batch, jax.random.PRNGKey(0))[0])(as_tree(weights)))["lm_head"]

    def err(precision):
        g = jax.grad(ref.loss)(weights, (batch[0], batch[1], None), cfg,
                               precision)["lm_head"]
        return float(jnp.linalg.norm(g_mine - g) / jnp.linalg.norm(g))

    assert err("float8") > 2 * err("float32")


@pytest.mark.parametrize("window", [None, WINDOW, 1, POSITIONS])
def test_mask_against_the_direct_formula(window):
    got = np.asarray(attention.allowed(jnp.arange(POSITIONS),
                                     jnp.arange(POSITIONS), window))
    want = np.array([[0 <= i - j and (window is None or i - j < window)
                      for j in range(POSITIONS)] for i in range(POSITIONS)])
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("window", [None, WINDOW, 20])
def test_blocked_attention_is_softmax_over_the_masked_scores(window):
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(2, POSITIONS, 2, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, POSITIONS, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, POSITIONS, 2, 16)), jnp.float32)
    got = attention.plain_attention(q, k, v, window, block=8)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k)
    ok = attention.allowed(jnp.arange(POSITIONS), jnp.arange(POSITIONS),
                           window)
    p = jax.nn.softmax(jnp.where(ok, scores, -jnp.inf), axis=-1)
    want = jnp.einsum("bhgqk,bkhd->bqhgd", p, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# (sequences, positions, query heads, q/k head size, window): the four
# transformer cells' attention layers, then the shapes the CPU tests lower
SPLASH_SHAPES = {
    "joyai_mla_dp1": (2, 8192, 32, 192, None),
    "mellum2_moe_dp1.full": (2, 8192, 32, 128, None),
    "mellum2_moe_dp1.window": (2, 8192, 32, 128, 1024),
    "trinity_gated_dp1.window": (2, 8192, 32, 128, 2048),
    "lfm2_conv_dp1": (2, 8192, 32, 64, None),
    "lowered.full": (2, 1024, 8, 128, None),
    "lowered.window": (2, 1024, 8, 128, 512),
    "lowered.mha": (2, 1024, 4, 192, None),
    "short.full": (2, 256, 4, 64, None),
    "short.window": (2, 64, 4, 16, 32),
    "many_sequences": (64, 8192, 32, 192, None),
    "long": (1, 65536, 32, 128, None),
}


@pytest.mark.parametrize("case", sorted(SPLASH_SHAPES))
def test_the_attention_kernels_sizes_follow_the_calls_shape(case):
    """Every block divides the positions and every compute block its memory
    block; a window layer keeps the two backward kernels over tiles no
    wider than 512 keys; a full layer takes the fused one with four partial
    sums of dq or fewer, in no more than a sixteenth of the chip's memory,
    out of memory blocks of four compute tiles or fewer, and the two
    kernels where no such block leaves that few."""
    b, s, heads, d, window = SPLASH_SHAPES[case]
    sizes = attention.splash_sizes(b, s, heads, d, window)
    memory = {"block_q": sizes.block_q, "block_kv": sizes.block_kv,
              "block_q_dkv": sizes.block_q_dkv,
              "block_kv_dkv": sizes.block_kv_dkv}
    tile = min(512, s)

    def held(block_kv_dkv):
        return b * (s // block_kv_dkv) * heads * s * d * 2

    if sizes.use_fused_bwd_kernel:
        assert window is None
        assert sizes.block_q_dq is None and sizes.block_kv_dq is None
        assert s // sizes.block_kv_dkv <= 4
        assert held(sizes.block_kv_dkv) <= 16 * 2 ** 30 // 16
        assert max(sizes.block_kv, sizes.block_kv_dkv) <= 4 * tile
        # a head of two lane tiles computes on half as many keys
        assert sizes.block_kv_dkv_compute == (
            256 if d > 128 and s >= 512 else tile)
    else:
        assert window or all(
            s // m > 4 or held(m) > 2 ** 30
            for m in range(tile, 4 * tile + 1, tile) if s % m == 0)
        memory.update(block_q_dq=sizes.block_q_dq,
                      block_kv_dq=sizes.block_kv_dq)
        assert set(memory.values()) == {tile}
        assert sizes.block_kv_dkv_compute == tile
    assert sizes.use_fused_bwd_kernel == (case not in (
        "mellum2_moe_dp1.window", "trinity_gated_dp1.window",
        "lowered.window", "short.window", "many_sequences", "long"))
    if s == 8192 and sizes.use_fused_bwd_kernel:      # the cells' full layers
        assert (sizes.block_kv, sizes.block_kv_dkv) == (2048, 2048)
    assert sizes.has_backward_blocks and sizes.block_kv_compute == tile
    for name, block in memory.items():
        assert 0 < block <= s and s % block == 0, (name, block)
    assert sizes.block_kv % sizes.block_kv_compute == 0
    assert sizes.block_kv_dkv % sizes.block_kv_dkv_compute == 0


@contextlib.contextmanager
def time_limit(seconds):
    """The body raises `TimeoutError` after `seconds` of wall time."""
    def late(*_):
        raise TimeoutError(f"over its limit of {seconds} s")
    before = signal.signal(signal.SIGALRM, late)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


@pytest.mark.parametrize("kv_heads,group,d,dv", [
    (2, 1, 192, 128), (1, 4, 128, 128)], ids=["mha_192_128", "mqa_128"])
def test_the_fused_backward_kernel_is_as_near_the_float32_gradients(
        kv_heads, group, d, dv, monkeypatch):
    """512 positions in tiles of 128, so that a full layer's fused kernel
    hands dq out as FOUR rounded partial sums, under the library's
    interpreter: dq, dk and dv lie no further from `plain_attention`'s
    float32 gradients than 1.5 times the two kernels' distance over the
    same tiles (dk and dv are the same arithmetic in both)."""
    from jax.experimental.pallas import tpu as pltpu
    monkeypatch.setattr(attention, "_SPLASH_BLOCK", 128)
    s = 512
    fused = attention.splash_sizes(1, s, kv_heads * group, d, None)
    assert fused.use_fused_bwd_kernel and s // fused.block_kv_dkv == 4
    two = attention.splash_sizes(1, s, kv_heads * group, d, s)  # a window's
    assert not two.use_fused_bwd_kernel and two.block_kv_dq == 128
    key = jax.random.key(42)
    q, k, v, do = (jax.random.normal(jax.random.fold_in(key, i), shape)
                   for i, shape in enumerate((
                       (1, s, kv_heads, group, d), (1, s, kv_heads, d),
                       (1, s, kv_heads, dv), (1, s, kv_heads, group, dv))))
    q = q * d ** -0.5

    def gradients(attend, *args):
        out, back = jax.vjp(lambda *qkv: attend(*qkv, None), *args[:3])
        return back(args[3].astype(out.dtype))

    with jax.default_matmul_precision("highest"):
        want = gradients(attention.plain_attention, q, k, v, do)
    low = [x.astype(jnp.bfloat16) for x in (q, k, v, do)]

    def distances(sizes):
        monkeypatch.setattr(attention, "splash_sizes", lambda *_: sizes)
        got = jax.jit(functools.partial(
            gradients, attention.splash_attention))(*low)
        return [float(jnp.linalg.norm((a.astype(jnp.float32) - b).ravel())
                      / jnp.linalg.norm(b.ravel()))
                for a, b in zip(got, want)]

    with time_limit(120), pltpu.force_tpu_interpret_mode():
        one_kernel, two_kernels = distances(fused), distances(two)
    for name, a, b in zip(("dq", "dk", "dv"), one_kernel, two_kernels):
        assert 0 < b < 0.01, (name, b)     # bfloat16 products, no more
        assert a <= 1.5 * b, (name, a, b)
    assert one_kernel[1:] == two_kernels[1:]


def test_default_rotary_against_the_direct_formula():
    d, theta = 16, 500000.0
    inv = rope.rope_inv_freq(d, theta)
    np.testing.assert_allclose(
        inv, [theta ** (-2 * i / d) for i in range(d // 2)], rtol=1e-12)
    x = np.random.default_rng(0).normal(size=(1, 6, 1, d)).astype(np.float32)
    got = np.asarray(rope.apply_rope(jnp.asarray(x), inv))
    for s in range(6):
        for i in range(d // 2):
            a, b = x[0, s, 0, i], x[0, s, 0, i + d // 2]
            c, sn = math.cos(s * inv[i]), math.sin(s * inv[i])
            np.testing.assert_allclose(
                [got[0, s, 0, i], got[0, s, 0, i + d // 2]],
                [a * c - b * sn, b * c + a * sn], atol=1e-5)


def test_yarn_rotary_against_the_direct_formula():
    """The published full-attention section at head size 128: pairs faster
    than 32 turns in 8192 positions keep their frequency, pairs slower than
    one turn are slowed 16 times, a linear ramp between (truncated ends);
    cos and sin carry the published attention factor."""
    d, theta, factor, orig = 128, 500000.0, 16.0, 8192
    got = rope.yarn_inv_freq(d, theta, factor, orig, 32.0, 1.0)

    def pair_of(turns):
        return d * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low, high = math.floor(pair_of(32.0)), math.ceil(pair_of(1.0))
    assert 0 < low < high < d // 2
    for i in range(d // 2):
        f = theta ** (-2 * i / d)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        assert got[i] == pytest.approx(f * (1 - ramp) + f / factor * ramp,
                                       rel=1e-12)
    assert got[0] == 1.0 and got[-1] == pytest.approx(
        theta ** (-(d - 2) / d) / factor)
    # the reference's own, written apart, agrees
    theirs, scale = ref.inv_frequencies(
        {"rope_type": "yarn", "rope_theta": theta, "factor": factor,
         "original_max_position_embeddings": orig, "beta_fast": 32,
         "beta_slow": 1, "attention_factor": 1.2772588722239782}, d)
    np.testing.assert_allclose(got, theirs, rtol=1e-12)
    assert scale == pytest.approx(0.1 * math.log(factor) + 1.0)
    x = jnp.ones((1, 3, 1, d), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(rope.apply_rope(x, got, scale))[0, 0, 0],
        np.full(d, scale), rtol=1e-6)


def _turn_by_pairs(x, inv, scale, interleave, rot):
    """The rotary turn by its definition, pair by pair, on the last `rot`
    entries of the last axis; float32."""
    x = x.astype(jnp.float32)
    off = x.shape[-1] - rot
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None, :]
    cos = (jnp.cos(ang) * scale)[None, :, None, :]
    sin = (jnp.sin(ang) * scale)[None, :, None, :]
    i = np.arange(rot // 2)
    first, second = ((off + 2 * i, off + 2 * i + 1) if interleave
                     else (off + i, off + i + rot // 2))
    a, b = x[..., first], x[..., second]
    return x.at[..., first].set(a * cos - b * sin).at[..., second].set(
        b * cos + a * sin)


@pytest.mark.parametrize("scale", [1.0, 1.2772588722239782])
@pytest.mark.parametrize("width,rot", [(128, 128), (64, 64), (192, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("interleave", [False, True])
def test_the_turn_and_its_cotangent_against_the_pairs_formula(
        interleave, dtype, width, rot, scale):
    """Both layouts, at a head size of a lane row, of half of one, and on
    the last 64 of a 192-wide head (the rest passes through): the result in
    float32 and the cotangent against the per-pair formula's."""
    rng = np.random.default_rng(width + rot)
    inv = rope.rope_inv_freq(rot, 500000.0)
    x = jnp.asarray(rng.normal(size=(2, 12, 3, width)), dtype)
    g = jnp.asarray(rng.normal(size=x.shape), jnp.float32)
    got, back = jax.vjp(
        lambda x: rope.apply_rope(x, inv, scale, interleave), x)
    want, want_back = jax.vjp(
        lambda x: _turn_by_pairs(x, inv, scale, interleave, rot), x)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    (dx,), (want_dx,) = back(g), want_back(g)
    assert dx.dtype == dtype
    # a bfloat16 cotangent is the float32 one rounded once
    np.testing.assert_allclose(
        np.asarray(dx, np.float32), np.asarray(want_dx, np.float32),
        atol=1e-5 if dtype == jnp.float32 else 2.0 ** -6)
    # the factor and the one rounding that the attention layers ask for
    scaled = rope.apply_rope(x, inv, scale, interleave, out_scale=0.25,
                                dtype=dtype)
    np.testing.assert_array_equal(
        np.asarray(scaled, np.float32),
        np.asarray((got * 0.25).astype(dtype), np.float32))


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside its equations."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (tuple, list))
                          else (value,)):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def assert_one_pass_turn(shape, rot, interleave, dtype=jnp.bfloat16):
    """On the jaxpr of `apply_rope` and of its cotangent at `shape` (abstract
    values: nothing is compiled): no cos or sin over more than the S x D/2
    angles, and on nothing of the input's size a concatenation, a split or
    a reshape to a minor axis of 2."""
    inv = rope.rope_inv_freq(rot, 500000.0)
    x = jax.ShapeDtypeStruct(shape, dtype)

    def both(x, g):
        y, back = jax.vjp(lambda x: rope.apply_rope(
            x, inv, 1.25, interleave, out_scale=0.5, dtype=dtype), x)
        return y, back(g)

    size, angles, seen = math.prod(shape), shape[1] * rot // 2, set()
    for eqn in _equations(jax.make_jaxpr(both)(x, x).jaxpr):
        name = eqn.primitive.name
        seen.add(name)
        sizes = [v.aval.size for v in (*eqn.invars, *eqn.outvars)
                 if hasattr(v.aval, "size")]
        if name in ("cos", "sin"):
            assert max(sizes) <= angles, eqn
        if name in ("concatenate", "split"):
            assert max(sizes) < size, eqn
        if name == "reshape" and max(sizes) >= size:
            assert eqn.outvars[0].aval.shape[-1] != 2, eqn
    assert "dot_general" in seen and "mul" in seen  # it did look inside


@pytest.mark.parametrize("shape", [(2, 8192, 32, 128), (2, 8192, 4, 128)])
def test_the_cells_half_split_turn_is_one_pass_at_full_width(shape):
    """`mellum2_moe_dp1`'s q and k: the per-head cosines and the half-lane
    layouts do not come back."""
    assert_one_pass_turn(shape, 128, False)


def _expert_layer(share, shares, experts=8, top=2):
    return moe.Experts(num_experts=experts, experts_per_token=top,
                           width=32, share=share, shares=shares,
                           dtype=jnp.float32)


def _uncut(x, router, w1, w3, w2, top=2):
    experts = router.shape[1]
    cfg = {"num_experts": experts, "num_experts_per_tok": top,
           "share": {"expert_share": 0, "expert_shares": 1}}
    weights = {"router": router, "w1": w1, "w3": w3, "w2": w2}
    return ref.experts(x.reshape(-1, x.shape[-1]), weights, "", cfg,
                       "float32").reshape(x.shape)


def _layer_weights(rng, experts):
    router = jnp.asarray(rng.normal(size=(64, experts)), jnp.float32)
    w1, w3 = (jnp.asarray(0.1 * rng.normal(size=(experts, 64, 32)),
                          jnp.float32) for _ in range(2))
    w2 = jnp.asarray(0.1 * rng.normal(size=(experts, 32, 64)), jnp.float32)
    return router, w1, w3, w2


# (experts, a token's, shares): the last two leave room for twice an even
# load's rows beside room for all, so the layer chooses as it runs
@pytest.mark.parametrize("experts,top,shares", [
    (8, 2, 1), (8, 2, 2), (8, 2, 4), (8, 2, 8), (16, 4, 4), (16, 4, 8)])
def test_the_shares_add_up(experts, top, shares):
    """The expert layer's outputs over all `shares`, each with its own
    experts, sum to the uncut reference's layer, and so do the gradients
    of what every share is given alike; the counters count every
    assignment once."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(2, POSITIONS, 64)), jnp.float32)
    router, w1, w3, w2 = _layer_weights(rng, experts)
    probe = jnp.asarray(rng.normal(size=x.shape), jnp.float32)
    held = experts // shares
    total, assigned, d_x, d_router = 0.0, 0.0, 0.0, 0.0
    for share in range(shares):
        mine = slice(share * held, (share + 1) * held)
        layer = _expert_layer(share, shares, experts, top)

        def part(x, router):
            y, counters = layer.apply(
                {"params": {"router": router, "w1": w1[mine],
                            "w3": w3[mine], "w2": w2[mine]}}, x)
            return jnp.sum(y * probe), (y, counters)

        (_, (y, counters)), (gx, gr) = jax.value_and_grad(
            part, argnums=(0, 1), has_aux=True)(x, router)
        total, d_x, d_router = total + y, d_x + gx, d_router + gr
        assigned += float(counters["moe_held_assignments"])
        assert 0.0 <= float(counters["moe_tokens_unserved"]) < 1.0
    assert assigned == 2 * POSITIONS * top      # every assignment, once
    want, (wx, wr) = jax.value_and_grad(
        lambda x, r: jnp.sum(_uncut(x, r, w1, w3, w2, top) * probe),
        argnums=(0, 1))(x, router)
    np.testing.assert_allclose(
        np.asarray(total), np.asarray(_uncut(x, router, w1, w3, w2, top)),
        atol=3e-5)
    np.testing.assert_allclose(np.asarray(d_x), np.asarray(wx), atol=2e-4)
    np.testing.assert_allclose(np.asarray(d_router), np.asarray(wr),
                               atol=2e-4)


@pytest.mark.parametrize("experts,top,shares,forced", [
    (8, 2, 1, (1,)),            # room for every assignment, and no other
    (16, 4, 4, (1,)),           # twice an even load's rows suffice
    (16, 4, 4, (0, 1, 2))])     # they do not: 3 T rows here, room for 2 T
def test_no_token_is_dropped_when_every_token_goes_to_one_expert(
        experts, top, shares, forced):
    """A router forced to send every token to the `forced` experts: each
    of them gets all T rows (no capacity), and the output is the
    reference's. A share that holds none of a token's experts serves it
    nothing."""
    rng = np.random.default_rng(13)
    tokens, held = 2 * POSITIONS, experts // shares
    x = jnp.asarray(rng.normal(size=(2, POSITIONS, 64)), jnp.float32)
    router, w1, w3, w2 = _layer_weights(rng, experts)
    # n2(h) is not applied here: a column along the tokens' common part
    x = x.at[..., 0].set(5.0)
    router = 0.01 * np.asarray(router)
    router[0, list(forced)] = 10.0
    router = jnp.asarray(router, jnp.float32)
    probs = jax.nn.softmax(x.reshape(tokens, 64) @ router, axis=-1)
    _, _, _, sizes, served = moe.route(probs, top, 0, held)
    assert [int(sizes[e]) for e in forced] == [tokens] * len(forced)
    assert bool(served.all())
    layer = _expert_layer(0, shares, experts, top)
    y, counters = layer.apply({"params": {
        "router": router, "w1": w1[:held], "w3": w3[:held],
        "w2": w2[:held]}}, x)
    assert float(counters["moe_held_assignments"]) == float(sizes.sum())
    assert float(counters["moe_load_max_over_mean"]) == pytest.approx(
        tokens / (float(sizes.sum()) / held))
    assert float(counters["moe_tokens_unserved"]) == 0.0
    rest = [_expert_layer(s, shares, experts, top).apply({"params": {
        "router": router, "w1": w1[s * held:(s + 1) * held],
        "w3": w3[s * held:(s + 1) * held],
        "w2": w2[s * held:(s + 1) * held]}}, x) for s in range(1, shares)]
    np.testing.assert_allclose(
        np.asarray(y + sum(r[0] for r in rest)),
        np.asarray(_uncut(x, router, w1, w3, w2, top)), atol=3e-5)
    # and its backward pass, whichever room the forward pass took
    got = jax.grad(lambda w: jnp.sum(layer.apply({"params": {
        "router": router, "w1": w, "w3": w3[:held], "w2": w2[:held]}},
        x)[0] ** 2))(w1[:held])
    rest_y = sum(r[0] for r in rest)
    want = jax.grad(lambda w: jnp.sum((_uncut(
        x, router, jnp.concatenate([w, w1[held:]]), w3, w2, top)
        - rest_y) ** 2))(w1[:held])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-3,
                               rtol=2e-3)
    for other, c in rest:
        unserved = float(c["moe_tokens_unserved"])
        assert 0.0 <= unserved < 1.0
        quiet = np.asarray(jnp.all(other == 0, axis=-1)).mean()
        assert quiet == pytest.approx(unserved)
    # the forced experts' rows alone are more than twice an even load's
    enough = -(-2 * tokens * top // shares // 8) * 8
    assert (float(sizes.sum()) > enough) == (len(forced) == 3)


def _sorted_by_group(group, held):
    """`route`'s sort of the assignments `group` [T, top] (a held expert's
    number, or `held` for an absent one)."""
    group = jnp.asarray(group, jnp.int32).reshape(-1)
    order = jnp.argsort(group, stable=True)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    sizes = jnp.sum(group[:, None] == jnp.arange(held)[None, :], axis=0,
                    dtype=jnp.int32)
    return order, inverse, sizes


# two blocks of tokens, each with `_ROOM` slots for its live rows, wherever
# the sorted rows' room is SUM_CAP: twice an even load's, as the cells'
SUM_TOP, SUM_HELD = 8, 8
SUM_TOKENS, SUM_CAP = moe._ROOM, 2 * moe._ROOM


def _routing(case):
    """[T, top] held-expert numbers (8: absent) and the sorted rows' room."""
    rng = np.random.default_rng(29)
    tokens, top, held = SUM_TOKENS, SUM_TOP, SUM_HELD
    per, room = tokens // 2, moe._ROOM
    assert moe._blocks(tokens, SUM_CAP) == (2, per, room)
    # each token's experts are distinct, as a top-k's are: 8 of 64
    group = np.stack([rng.permutation(64)[:top] for _ in range(tokens)])
    group = np.where(group < held, group, held)
    cap = SUM_CAP
    if case == "all_of_a_token_and_none":
        group[0] = np.arange(top)               # every assignment live
        group[1] = held                         # none
        group[tokens - 1] = np.arange(top)[::-1]    # the last token too
    elif case == "neighbours_in_a_group":
        # token 5's two rows are neighbours in the sorted order (the end of
        # expert 2's rows, the start of expert 3's: tokens 0-4 get neither)
        group[:5] = held
        group[5] = [2, 3] + [held] * (top - 2)
        # and one expert twice: what no top-k gives, and a sum all the same
        group[9] = [4, 4] + [held] * (top - 2)
    elif case == "live_rows_exactly_cap":
        group[:] = held
        group[:, 2] = rng.integers(0, held, tokens)     # two a token:
        group[:, 6] = (group[:, 2] + 3) % held      # every slot of both blocks
    elif case == "cap_is_every_assignment":
        cap = tokens * top
    elif case == "most_of_the_load_in_one_block":
        group[per:] = held
        group[per:per + room // top - 4] = np.arange(top)   # all but 32 slots
    elif case == "tokens_no_multiple_of_the_blocks":
        group = group[:tokens - 5]      # the last block is a token short
    elif case == "a_block_fuller_than_its_slots":
        group[:] = held
        group[:room // top + 8] = np.arange(top)    # 64 rows more than slots
    else:
        assert case == "even_load"
    return group, cap


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", [
    "even_load", "all_of_a_token_and_none", "neighbours_in_a_group",
    "live_rows_exactly_cap", "cap_is_every_assignment",
    "most_of_the_load_in_one_block", "tokens_no_multiple_of_the_blocks",
    "a_block_fuller_than_its_slots"])
def test_the_sum_back_to_tokens_against_the_dense_formula(case, dtype):
    """`to_tokens` and the cotangent of `to_rows` against a dense [T, cap]
    matrix of weights times the rows in float64: forward, and both
    cotangents (`d_r`, `d_scale` through `to_tokens`; `d_x` through
    `to_rows`), whichever way `_summed` goes for `cap` and for the blocks'
    load: a block with more live rows than slots loses none (the sum takes
    a row for every assignment that step)."""
    top, held, h = SUM_TOP, SUM_HELD, 24
    group, cap = _routing(case)
    tokens = group.shape[0]
    order, inverse, sizes = _sorted_by_group(group, held)
    first, live = order[:cap], inverse < jnp.sum(sizes)
    n_live = int(sizes.sum())
    assert n_live <= cap
    used = float(moe.room_used(cap, top, inverse, sizes))
    if case == "a_block_fuller_than_its_slots":
        assert used == (moe._ROOM + 64) / moe._ROOM
    else:
        assert used <= 1.0
    if case == "live_rows_exactly_cap":
        assert n_live == cap and used == 1.0
    rng = np.random.default_rng(31)
    r = jnp.asarray(rng.normal(size=(cap, h)), dtype)
    scale = jnp.asarray(rng.uniform(0.05, 1.0, size=tokens * top),
                        jnp.float32)
    g = jnp.asarray(rng.normal(size=(tokens, h)), jnp.float32)
    # the dense matrices: weights[t, i] of sorted row i in token t's sum
    weights = np.zeros((tokens, cap))
    ones = np.zeros((tokens, cap))
    for a in np.flatnonzero(np.asarray(live)):
        weights[a // top, int(inverse[a])] += float(scale[a])
        ones[a // top, int(inverse[a])] += 1.0
    r64 = np.asarray(r.astype(jnp.float32), np.float64)
    one_rounding = 2.0 ** -8 if dtype == jnp.bfloat16 else 1e-6

    y, back = jax.vjp(lambda r, scale: moe.to_tokens(
        r, scale, first, inverse, live, top), r, scale)
    assert y.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(y, np.float64), weights @ r64,
                               atol=2e-6, rtol=2e-6)
    d_r, d_scale = back(g)
    assert d_r.dtype == dtype
    g64 = np.asarray(g, np.float64)
    np.testing.assert_allclose(
        np.asarray(d_r.astype(jnp.float32), np.float64), weights.T @ g64,
        atol=one_rounding, rtol=one_rounding)
    want = np.zeros(tokens * top)
    for a in np.flatnonzero(np.asarray(live)):
        want[a] = g64[a // top] @ r64[int(inverse[a])]
    np.testing.assert_allclose(np.asarray(d_scale, np.float64), want,
                               atol=2e-5, rtol=2e-5)

    x = jnp.asarray(rng.normal(size=(tokens, h)), dtype)
    rows, back = jax.vjp(lambda x: moe.to_rows(
        x, first, inverse, live, top), x)
    np.testing.assert_array_equal(
        np.asarray(rows.astype(jnp.float32)),
        np.asarray(x.astype(jnp.float32))[np.asarray(first) // top])
    g_rows = jnp.asarray(rng.normal(size=(cap, h)), dtype)
    d_x, = back(g_rows)
    assert d_x.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(d_x.astype(jnp.float32), np.float64),
        ones @ np.asarray(g_rows.astype(jnp.float32), np.float64),
        atol=4 * one_rounding, rtol=one_rounding)
    # a token none of whose experts is held gets exactly nothing
    if case == "all_of_a_token_and_none":
        assert not np.asarray(y[1]).any() and not np.asarray(d_x[1]).any()
        assert np.asarray(y[0]).any() and np.asarray(y[tokens - 1]).any()


def _rows_of_every_assignment(jaxpr, full, h):
    """The primitives in `jaxpr` with an operand or a result of `full` rows
    of width `h` or more, and every primitive it holds."""
    big, seen = [], set()
    for eqn in _equations(jaxpr):
        seen.add(eqn.primitive.name)
        if any(getattr(v.aval, "size", 0) >= full * h
               for v in (*eqn.invars, *eqn.outvars)):
            big.append(eqn.primitive.name)
    return big, seen


@pytest.mark.parametrize("which", ["to_tokens", "to_rows_cotangent"])
def test_the_cells_sum_reads_the_rows_that_are_there(which):
    """`mellum2_moe_dp1`'s expert layer (T 16 384, top 8, h 2304, room for
    32 768 sorted rows): the sum is a `cond` whose side for blocks that fit
    holds no gather, nor anything else, of a row for every one of the
    131 072 assignments, and nothing outside the `cond` does; its other
    side is the gather that was there (the count looks where the rows
    are)."""
    tokens, top, h, cap = 16384, 8, 2304, 32768
    full = tokens * top
    shape = jax.ShapeDtypeStruct
    index = (shape((cap,), jnp.int32), shape((full,), jnp.int32),
             shape((full,), jnp.bool_))
    rows = shape((cap, h), jnp.bfloat16)
    if which == "to_tokens":
        jaxpr = jax.make_jaxpr(
            lambda r, scale, first, inverse, live: moe.to_tokens(
                r, scale, first, inverse, live, top))(
            rows, shape((full,), jnp.float32), *index).jaxpr
    else:
        jaxpr = jax.make_jaxpr(
            lambda x, g, first, inverse, live: jax.vjp(
                lambda x: moe.to_rows(x, first, inverse, live, top),
                x)[1](g))(shape((tokens, h), jnp.bfloat16), rows,
                          *index).jaxpr
    cond, = [e for e in _equations(jaxpr) if e.primitive.name == "cond"]
    gathered, banded = (b.jaxpr for b in cond.params["branches"])
    big, seen = _rows_of_every_assignment(banded, full, h)
    assert not big and {"gather", "dot_general", "cumsum"} <= seen
    big, seen = _rows_of_every_assignment(gathered, full, h)
    assert "gather" in big and "cumsum" not in seen
    outside = [e for e in _equations(jaxpr)
               if e not in set(_equations(gathered))]
    assert not any(getattr(v.aval, "size", 0) >= full * h for e in outside
                   for v in (*e.invars, *e.outvars))


def test_through_the_trainer_for_a_few_sparse_steps(tmp_path):
    """`--dnn mellum2 --dataset ptb` builds through `make_trainer` like
    every other model, trains sparse steps on two workers, and its `train`
    record carries the router's counters."""
    from gaussiank_sgd_tpu import train
    kw = {"hidden_size": 64, "num_layers": 4, "num_heads": 4,
          "num_kv_heads": 2, "head_dim": 16, "sliding_window": WINDOW,
          "num_experts": 8, "experts_per_token": 2, "expert_width": 32,
          "expert_share": 0, "expert_shares": 2, "yarn_original_max": 16,
          "seq_len": POSITIONS}
    data = {"vocab_size": VOCAB, "bptt": POSITIONS,
            "synthetic_tokens_n": 4 * (12 * POSITIONS + 1)}
    trainer = train.make_trainer([
        "--dnn", "mellum2", "--dataset", "ptb", "--nworkers", "2",
        "--batch-size", "2", "--compressor", "auto", "--density", "0.01",
        "--lr", "0.05", "--weight-decay", "0.0001", "--compute-dtype",
        "float32", "--max-steps", "8", "--log-every", "2",
        "--model-kwargs", json.dumps(kw), "--dataset-kwargs",
        json.dumps(data), "--output-dir", str(tmp_path)])
    try:
        assert trainer.spec.name == "mellum2" and trainer.spec.task == "lm"
        first = trainer.train(2)
        rec = trainer.train(4)
    finally:
        trainer.close()
    assert np.isfinite(rec["loss"]) and rec["loss"] < first["loss"] + 0.5
    assert rec["num_selected"] > 0
    # 2 sequences x 32 positions x top-2 a worker, half the experts held
    assert 0 < rec["moe_held_assignments"] <= 4 * 2 * POSITIONS * 2
    assert rec["moe_load_max_over_mean"] >= 1.0
    assert 0.0 <= rec["moe_tokens_unserved"] < 1.0
    with open(os.path.join(trainer.run_dir, "metrics.jsonl")) as f:
        trains = [r for r in map(json.loads, f) if r.get("event") == "train"]
    assert trains and all("moe_held_assignments" in r for r in trains)
