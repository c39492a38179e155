"""Plain reference of the `qwen3_next_80b_a3b` configuration: Qwen3-Next-80B-A3B
(Qwen/Qwen3-Next-80B-A3B-Instruct, config.json, `model_type` `qwen3_next`),
one chip's share of a group of chips that divide each layer by experts and
by vocabulary rows.

Every number is the configuration file's, under the published config's own
keys (`hidden_size`, `head_dim`, `num_attention_heads`,
`num_key_value_heads`, `partial_rotary_factor`, `rope_theta`,
`rms_norm_eps`, `full_attention_interval`, `linear_conv_kernel_dim`,
`linear_key_head_dim`, `linear_value_head_dim`, `linear_num_key_heads`,
`linear_num_value_heads`, `moe_intermediate_size`,
`shared_expert_intermediate_size`, `num_experts_per_tok`,
`norm_topk_prob`); `num_hidden_layers`, `num_experts` (the experts HELD
here) and `vocab_size` (the rows held) are the cut ones,
`published.num_experts` is the router's width, and `share` says which
experts are held (`expert_share` of `expert_shares`) and which of the
published layers (`layers`; layer i is `full_attention` where `(i + 1) %
full_attention_interval == 0`, else `linear_attention`).

Per layer (every norm ZERO-CENTRED: `x / rms(x) * (1 + w)`, eps
`rms_norm_eps`; no bias anywhere; `x` a token's stream), as transformers'
`modeling_qwen3_next.py` has it:

    h = x + mixer(norm_1(x));      y = h + moe(norm_2(h))

    linear_attention, u = norm_1(x):
        [q | k | v | z] = u W_qkvz;   [b | a] = u W_ba
        [q | k | v]_t <- silu(sum_{j < L} w_j [q | k | v]_{t-(L-1)+j})
            (a depthwise causal convolution, zeros before the sequence)
        beta = sigmoid(b);   g = -exp(A_log) softplus(a + dt_bias)
        q <- l2norm(q) / sqrt(d_k);   k <- l2norm(k)
            (x / sqrt(sum x^2 + 1e-6) over a head's entries; a key head
            serves `value heads / key heads` value heads, neighbours)
        per value head, S_0 = 0 [d_v, d_k]:
            S_t = exp(g_t) S_{t-1} - beta_t (exp(g_t) S_{t-1} k_t - v_t) k_t^T
            o_t = S_t q_t                       TOKEN BY TOKEN (`delta_rule`)
        mixer = (rmsnorm(o) w_n silu(z)) W_o    (a head at a time; w_n NOT
                                                zero-centred)
    full_attention:
        q, k = turn(norm(u W_q)), turn(norm(u W_k))     (zero-centred norm
            over each head's entries; `turn`: rotary at `rope_theta` on the
            FIRST `partial_rotary_factor * head_dim` entries, pairs (i, i +
            half of them))
        o = concat_h(softmax_causal(q_h k_j^T / sqrt(d)) v_j)
        mixer = (sigmoid(u W_g) * o) W_o
    moe:
        p = softmax(x W_r) in float32 over all the router's outputs;
        C = the `num_experts_per_tok` largest
        sigmoid(x w_g) shared(x) + sum over held e in C of
            (p_e / sum_C p) F_e(x),     F = W2 (silu(W1 .) * W3 .)

After the last layer a zero-centred norm and an untied head.

Departures from the published description (the file's `assumed`):
  * what absent experts would add to `y` is left out, and the partial sum
    goes on to the next layer, in the program alike (the model-configs
    guide, section 4): on one chip there is no exchange;
  * the router's product is float32 at `highest` in every `precision` (the
    program holds it so too); every other product takes the rounding of
    `precision` (`common._round_forward`, `_round_backward`). The rule's
    own sums (`S k`, `S q`) are float32 multiply-adds and no matrix
    product; sigmoids, softplus, norms, the taps and the turn are
    elementwise, float32 in every precision;
  * the multi-token-prediction layer is left out (no key for its shape).

So that float32 at 8192 positions fits the chip beside the check's arrays:
the rule in stretches of `_RULE_BLOCK` tokens, each recomputed in the
backward pass (the scan over stretches keeps one state a stretch, the scan
inside one a token); attention in blocks of queries (`lax.map`), each
against all keys under the mask; the head and the cross-entropy in blocks
of tokens; each layer recomputed in the backward pass, and in it the
linear mixer one sequence after the other. The held experts run
one after the other over all tokens, each token's term weighted by its gate
(zero where the token was not routed to the expert).

Parameters are a flat {path: array} dict under the program's own paths
(`layers_<i>/mixer/...` and `layers_<i>/routed/...`, the two halves the
program recomputes apart, i the layer's place among those held); nothing is read
from the program. Held layers that follow one another and are of one kind
(the three linear ones) are stacked and run as one `lax.scan`, though the
program unrolls them: one layer's text for the compiler, the same
arithmetic.
"""

from __future__ import annotations

import itertools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.scipy.special import ndtr

from . import common as C

_QUERY_BLOCK = 256
_TOKEN_BLOCK = 2048     # tokens of the head and the loss at a time
_RULE_BLOCK = 64        # tokens of the rule between two kept states
_L2_EPS = 1e-6
LINEAR, FULL = "linear_attention", "full_attention"


def layers(cfg: dict) -> list:
    """The kind of each layer held here, in order."""
    held = cfg["share"]["layers"]
    assert len(held) == cfg["num_hidden_layers"]
    every = cfg["full_attention_interval"]
    return [FULL if (i + 1) % every == 0 else LINEAR for i in held]


def _widths(cfg: dict):
    """(key heads, value heads, key head size, value head size)."""
    return (cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"])


def _layer_plan(p: str, cfg: dict, kind: str):
    h, std = cfg["hidden_size"], 0.02
    plan = [(p + "mixer/input_norm/scale", (h,), 0.0)]
    if kind == LINEAR:
        hk, hv, dk, dv = _widths(cfg)
        keys, values = hk * dk, hv * dv
        m = p + "mixer/linear_attn/"
        plan += [(m + "in_proj_qkvz/kernel", (h, 2 * keys + 2 * values), std),
                 (m + "in_proj_ba/kernel", (h, 2 * hv), std),
                 (m + "conv_taps",
                  (2 * keys + values, cfg["linear_conv_kernel_dim"]), std),
                 (m + "A_log", (hv,), "log_uniform"),
                 (m + "dt_bias", (hv,), None),
                 (m + "norm_scale", (dv,), None),
                 (m + "out_proj/kernel", (values, h), std)]
    else:
        d = cfg["head_dim"]
        heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        a = p + "mixer/attn/"
        plan += [(a + "q_proj/kernel", (h, heads, d), std),
                 (a + "k_proj/kernel", (h, kv, d), std),
                 (a + "v_proj/kernel", (h, kv, d), std),
                 (a + "gate_proj/kernel", (h, heads, d), std),
                 (a + "q_layernorm", (d,), 0.0),
                 (a + "k_layernorm", (d,), 0.0),
                 (a + "o_proj/kernel", (heads, d, h), std)]
    held, width = cfg["num_experts"], cfg["moe_intermediate_size"]
    shared = cfg["shared_expert_intermediate_size"]
    r = p + "routed/"
    return plan + [
        (r + "post_attn_norm/scale", (h,), 0.0),
        (r + "moe/router", (h, cfg["published"]["num_experts"]), std),
        (r + "moe/w1", (held, h, width), std),
        (r + "moe/w3", (held, h, width), std),
        (r + "moe/w2", (held, width, h), std),
        (r + "moe/shared/w1", (h, shared), std),
        (r + "moe/shared/w3", (h, shared), std),
        (r + "moe/shared/w2", (shared, h), std),
        (r + "moe/shared_gate", (h,), std)]


def _plan(cfg: dict):
    """(path, shape, how it starts: a normal draw's deviation, None for
    ones, 0.0 for zeros, "log_uniform" for the log of uniform(0, 16)) in
    order of use."""
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    # Unit embeddings (assumed), as the sibling configurations': at the
    # products' 0.02 the seeded router does not tell tokens apart
    plan = [("embed/embedding", (vocab, h), 1.0)]
    for i, kind in enumerate(layers(cfg)):
        plan += _layer_plan(f"layers_{i}/", cfg, kind)
    return plan + [("norm/scale", (h,), 0.0), ("lm_head", (h, vocab), 0.02)]


def init_params(key, cfg: dict) -> dict:
    """Seeded weights: normal(0, 0.02) for every product, the taps and the
    shared expert's gate (assumed: the family's `initializer_range`),
    normal(0, 1) embedding, zero scales of the zero-centred norms, unit
    `norm_scale` and `dt_bias`, `A_log` the log of uniform(0, 16). Trace it
    under one `jax.jit`. Every drawn leaf is cut from ONE normal draw of
    the generator the chip has in hardware, in the order of `_plan`; the
    uniform is the normal's own distribution function of it."""
    plan = _plan(cfg)
    sizes = [math.prod(shape) if how else 0 for _, shape, how in plan]
    if not jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.wrap_key_data(key)
    key = jax.random.wrap_key_data(
        jnp.tile(jax.random.key_data(key).reshape(-1)[:2], 2), impl="rbg")
    draw = jax.random.normal(key, (sum(sizes),), jnp.float32)
    out, at = {}, 0
    for (path, shape, how), size in zip(plan, sizes):
        if how is None:
            out[path] = jnp.ones(shape, jnp.float32)
            continue
        if how == 0.0:
            out[path] = jnp.zeros(shape, jnp.float32)
            continue
        cut = draw[at:at + size]
        at += size
        # a leaf with few columns (the taps' 4) is cut as its transpose: the
        # TPU compiler moves such a reshape before the slice, and the WHOLE
        # draw as `[n / 4, 4]` is laid out a row a tile of 128 lanes (54 GB)
        cut = (cut.reshape(shape[::-1]).T if len(shape) == 2
               and shape[1] < 128 else cut.reshape(shape))
        out[path] = (jnp.log(jnp.maximum(16.0 * ndtr(cut), 1e-30))
                     if how == "log_uniform" else how * cut)
    return out


def param_shapes(cfg: dict) -> dict:
    return {path: shape for path, shape, _ in _plan(cfg)}


def _product(spec: str, a, b, precision: str):
    """`einsum(spec, a, b)` with both operands and the cotangent held as
    `precision` holds them, accumulated in float32."""
    a, b = C._round_forward(a, precision), C._round_forward(b, precision)
    return C._round_backward(
        jnp.einsum(spec, a, b, precision=C.HIGHEST), precision)


def _products(spec: str, a, bs, precision: str):
    """`[_product(spec, a, b, precision) for b in bs]` as ONE product of
    `a` with the `bs` side by side along their second axis: entry for entry
    the same sums, every operand and every cotangent still rounded on its
    own, and a `highest` product fewer for the compiler each time (this
    file's gradient program compiles in 49.7 s device-less for a v5e with
    them, in 65.1 without; PERF.md section 4, PR 44)."""
    ins, out = spec.split("->")
    at = out.index(ins.split(",")[1][1])
    a = C._round_forward(a, precision)
    both = jnp.einsum(spec, a, jnp.concatenate(
        [C._round_forward(b, precision) for b in bs], axis=1),
        precision=C.HIGHEST)
    ends = list(itertools.accumulate(b.shape[1] for b in bs))[:-1]
    return [C._round_backward(y, precision)
            for y in jnp.split(both, ends, axis=at)]


def rms_norm(x, scale, eps: float):
    """Zero-centred: times `1 + scale`."""
    return (x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * (1.0 + scale))


def l2_norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _L2_EPS)


def causal_taps(x, taps):
    """x [B, S, w], taps [w, L]: `sum_j taps[:, j] x_{t-(L-1)+j}`, zeros
    before the sequence."""
    s, length = x.shape[1], taps.shape[1]
    filled = jnp.pad(x, ((0, 0), (length - 1, 0), (0, 0)))
    return sum(taps[:, j] * filled[:, j:j + s] for j in range(length))


def delta_rule(q, k, v, g, beta):
    """The gated delta rule, token by token. q, k [B, S, H, dk], v [B, S,
    H, dv], g and beta [B, S, H] -> o [B, S, H, dv]. `S` [B, H, dv, dk]
    starts at zero; the sums `S k` and `S q` are float32 multiply-adds."""
    b, s, h, dk = q.shape
    block = _RULE_BLOCK if s % _RULE_BLOCK == 0 else s

    def token(state, at):
        q_t, k_t, v_t, g_t, beta_t = at
        state = state * jnp.exp(g_t)[..., None, None]
        held = jnp.sum(state * k_t[..., None, :], axis=-1)
        state = state - (beta_t[..., None] * (held - v_t))[
            ..., :, None] * k_t[..., None, :]
        return state, jnp.sum(state * q_t[..., None, :], axis=-1)

    @jax.checkpoint
    def stretch(state, of):
        return lax.scan(token, state, of)

    def by_stretch(a):
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape(s // block, block, *a.shape[1:])

    _, o = lax.scan(stretch, jnp.zeros((b, h, v.shape[-1], dk), jnp.float32),
                    tuple(by_stretch(a) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape(s, b, h, -1), 0, 1)


def linear_attention(u, weights: dict, cfg: dict, precision: str):
    """u [B, S, h] -> [B, S, h]; `weights` under the module's own paths. No
    token sees another sequence's, so the sequences go one after the other,
    each recomputed in the backward pass: half of this mixer's float32
    arrays at two (the gradient program's temporaries 8.29 GB for 11.45,
    device-less for a v5e, for 6-15 s more of compiling)."""
    one = jax.checkpoint(lambda u_1: _linear_attention(
        u_1[None], weights, cfg, precision)[0])
    return lax.map(one, u)


def _linear_attention(u, weights: dict, cfg: dict, precision: str):
    b, s, _ = u.shape
    hk, hv, dk, dv = _widths(cfg)
    keys, values = hk * dk, hv * dv
    qkvz, ba = _products("bsh,hn->bsn", u, [
        weights["in_proj_qkvz/kernel"], weights["in_proj_ba/kernel"]],
        precision)
    qkv = jax.nn.silu(causal_taps(qkvz[..., :2 * keys + values],
                                  weights["conv_taps"]))
    z = qkvz[..., 2 * keys + values:].reshape(b, s, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(weights["A_log"]) * jax.nn.softplus(
        ba[..., hv:] + weights["dt_bias"])
    q = l2_norm(qkv[..., :keys].reshape(b, s, hk, dk)) / math.sqrt(dk)
    k = l2_norm(qkv[..., keys:2 * keys].reshape(b, s, hk, dk))
    q, k = (jnp.repeat(a, hv // hk, axis=2) for a in (q, k))
    o = delta_rule(q, k, qkv[..., 2 * keys:].reshape(b, s, hv, dv), g, beta)
    o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                      + cfg["rms_norm_eps"])
    y = o * weights["norm_scale"] * jax.nn.silu(z)
    return _product("bsn,nh->bsh", y.reshape(b, s, values),
                    weights["out_proj/kernel"], precision)


def rotate_first(x, theta: float, turned: int):
    """x [B, S, heads, d]: of the first `turned` entries, (j, j + turned/2)
    turned by the angle `s * theta ** (-2j / turned)` at position s; the
    rest as they are."""
    half = turned // 2
    inv_freq = jnp.asarray([theta ** (-2.0 * j / turned)
                            for j in range(half)], jnp.float32)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = (f(ang)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    a, c = x[..., :half], x[..., half:turned]
    return jnp.concatenate([a * cos - c * sin, c * cos + a * sin,
                            x[..., turned:]], axis=-1)


def attention(q, k, v, precision: str):
    """q [B, S, H, d], k and v [B, S, Hkv, d] -> [B, S, H, d], causal,
    scaled by 1 / sqrt(d); key/value head j serves query heads `j * H /
    Hkv` up to the next one's first. The queries are filled up to whole
    blocks with rows that are thrown away."""
    b, s, heads, d = q.shape
    group = heads // k.shape[2]
    block = min(_QUERY_BLOCK, s)
    fill = -s % block
    q = jnp.pad(q, ((0, 0), (0, fill), (0, 0), (0, 0)))
    q = q.reshape(b, s + fill, k.shape[2], group, d)
    keys = jnp.arange(s)

    @jax.checkpoint
    def one(i):
        q_i = lax.dynamic_slice_in_dim(q, i * block, block, axis=1)
        scores = _product("bqhgd,bkhd->bhgqk", q_i, k, precision) / math.sqrt(
            d)
        seen = (i * block + jnp.arange(block))[:, None] >= keys[None, :]
        scores = jnp.where(seen[None, None, None], scores, -jnp.inf)
        return _product("bhgqk,bkhd->bqhgd", jax.nn.softmax(scores, axis=-1),
                        v, precision)

    out = lax.map(one, jnp.arange((s + fill) // block))
    return jnp.moveaxis(out, 0, 1).reshape(b, s + fill, heads, d)[:, :s]


def gated_attention(u, weights: dict, cfg: dict, precision: str):
    """u [B, S, h] -> [B, S, h]; `weights` under the module's own paths."""
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    assert cfg.get("rope_scaling") is None
    turned = int(cfg["head_dim"] * cfg["partial_rotary_factor"])
    q, k, v, gate = _products("bsh,hnd->bsnd", u, [
        weights[name + "_proj/kernel"] for name in ("q", "k", "v", "gate")],
        precision)
    q = rotate_first(rms_norm(q, weights["q_layernorm"], eps), theta, turned)
    k = rotate_first(rms_norm(k, weights["k_layernorm"], eps), theta, turned)
    out = attention(q, k, v, precision)
    return _product("bsnd,ndh->bsh", jax.nn.sigmoid(gate) * out,
                    weights["o_proj/kernel"], precision)


def gated(x, w1, w3, w2, precision: str):
    gate, up = _products("th,hw->tw", x, [w1, w3], precision)
    return _product("tw,wh->th", jax.nn.silu(gate) * up, w2, precision)


def gates(x, router, cfg: dict):
    """(each token's chosen experts [T, top], their gates [T, top]) over
    ALL the router's outputs."""
    probs = jax.nn.softmax(jnp.dot(x, router, precision=C.HIGHEST), axis=-1)
    picked, chosen = lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return chosen, picked


def experts(x, weights: dict, cfg: dict, precision: str):
    """x [T, h] -> the held experts' part of the layer's output, and the
    gated shared expert's."""
    held = cfg["num_experts"]
    first = cfg["share"]["expert_share"] * held
    chosen, weight = gates(x, weights["router"], cfg)

    @jax.checkpoint
    def add_expert(y, packed):
        e, w1, w3, w2 = packed
        g_e = jnp.sum(jnp.where(chosen == first + e, weight, 0.0), axis=-1)
        return y + g_e[:, None] * gated(x, w1, w3, w2, precision), None

    # one expert after the other, as a loop of the program and not of its
    # text, each recomputed in the backward pass
    y, _ = lax.scan(add_expert, jnp.zeros_like(x),
                    (jnp.arange(held), weights["w1"], weights["w3"],
                     weights["w2"]))
    share = jax.nn.sigmoid(jnp.sum(x * weights["shared_gate"], axis=-1))
    return y + share[:, None] * gated(
        x, weights["shared/w1"], weights["shared/w3"], weights["shared/w2"],
        precision)


def _under(params: dict, prefix: str) -> dict:
    return {p[len(prefix):]: v for p, v in params.items()
            if p.startswith(prefix)}


def layer(x, weights: dict, cfg: dict, precision: str, kind: str):
    """x [B, S, h] through one layer; `weights` under the layer's own
    paths."""
    b, s, _ = x.shape
    eps = cfg["rms_norm_eps"]
    u = rms_norm(x, weights["mixer/input_norm/scale"], eps)
    if kind == LINEAR:
        x = x + linear_attention(u, _under(weights, "mixer/linear_attn/"),
                                 cfg, precision)
    else:
        x = x + gated_attention(u, _under(weights, "mixer/attn/"), cfg,
                                precision)
    n = rms_norm(x, weights["routed/post_attn_norm/scale"], eps
                 ).reshape(b * s, -1)
    return x + experts(n, _under(weights, "routed/moe/"), cfg,
                       precision).reshape(x.shape)


def _runs(kinds: list) -> list:
    """[(first, count)] of the stretches of neighbours of one kind."""
    out = []
    for i, kind in enumerate(kinds):
        if out and kinds[out[-1][0]] == kind:
            out[-1][1] += 1
        else:
            out.append([i, 1])
    return [tuple(r) for r in out]


def head_loss(x, head, targets, precision: str):
    """Mean cross-entropy of x [T, h] through `head` [h, V] against
    `targets` [T], `_TOKEN_BLOCK` tokens' logits at a time (filled up to
    whole blocks with tokens that count for nothing)."""
    tokens = x.shape[0]
    block = min(_TOKEN_BLOCK, tokens)
    fill = -tokens % block
    x = jnp.pad(x, ((0, fill), (0, 0))).reshape(-1, block, x.shape[-1])
    targets = jnp.pad(targets, (0, fill)).reshape(-1, block)
    counts = (jnp.arange(tokens + fill) < tokens).reshape(-1, block)

    @jax.checkpoint
    def one(packed):
        x_i, t_i, c_i = packed
        logits = _product("th,hv->tv", x_i, head, precision)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, t_i[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(c_i, lse - picked, 0.0))

    return jnp.sum(lax.map(one, (x, targets, counts))) / tokens


def loss(params: dict, batch, cfg: dict, precision: str = "float32"):
    """batch = (token ids i32 [B, S], next ids i32 [B, S], None)."""
    tokens, targets, _ = batch
    assert not cfg.get("tie_word_embeddings", False)
    kinds = layers(cfg)
    # each layer is recomputed in the backward pass
    one = jax.checkpoint(
        lambda x, w, kind: layer(x, w, cfg, precision, kind),
        static_argnums=2)
    x = params["embed/embedding"][tokens]
    for first, count in _runs(kinds):
        kind = kinds[first]
        each = [_under(params, f"layers_{i}/")
                for i in range(first, first + count)]
        if count == 1:
            x = one(x, each[0], kind)
            continue
        # the same leaves and next to each other: stacked here, one after
        # the other as a loop of the program and not of its text
        stacked = {p: jnp.stack([w[p] for w in each]) for p in each[0]}
        x, _ = lax.scan(lambda x, w: (one(x, w, kind), None), x, stacked)
    x = rms_norm(x, params["norm/scale"], cfg["rms_norm_eps"])
    return head_loss(x.reshape(-1, x.shape[-1]), params["lm_head"],
                     targets.reshape(-1), precision)
