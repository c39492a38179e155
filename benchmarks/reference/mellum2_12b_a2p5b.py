"""Plain reference of the `mellum2_12b_a2p5b` configuration: Mellum 2
(JetBrains/Mellum2-12B-A2.5B-Instruct, config.json), one chip's share of a
group of chips that divide each layer by experts and by vocabulary rows.

Every number is the configuration file's, under the published config's own
keys (`hidden_size`, `num_attention_heads`, `num_key_value_heads`,
`head_dim`, `moe_intermediate_size`, `num_experts_per_tok`, `sliding_window`,
`rms_norm_eps`, `rope_parameters`, `layer_types`); `num_hidden_layers`,
`num_experts` (the experts HELD here) and `vocab_size` (the rows held) are
the cut ones, `published.num_experts` is the router's width and `share`
says which experts are held (`expert_share` of `expert_shares`).

Per layer (pre-norm, RMSNorm without bias, no bias anywhere):

    a = x + (softmax(rope(n1(x) Wq) rope(n1(x) Wk)^T / sqrt(d) + mask)
             (n1(x) Wv)) Wo
    y = a + sum over held e of p_e W2_e (silu(W1_e n2(a)) * W3_e n2(a))

`p` is the softmax over ALL the router's outputs in float32, its
`num_experts_per_tok` largest kept and renormalised to sum 1; each key/value
head serves `num_attention_heads / num_key_value_heads` query heads; a
`sliding_attention` layer sees keys `0 <= i - j < sliding_window` under
default rotary positions, a `full_attention` layer every earlier key under
yarn-scaled ones (transformers' `_compute_yarn_parameters`; `truncate` is
absent from the config, so its default, true; cos and sin times the
published `attention_factor`). The loss is the mean cross-entropy of a
token over the rows held.

Departures from the published description (the file's `assumed`):
  * no QK-norm (the config has no key for one);
  * no shared expert ("0 shared"), no auxiliary load-balance loss (the
    config gives no coefficient), no multi-token-prediction head (the
    config has no key for one: the config is trusted);
  * what absent experts would add to `y` is left out, and the partial sum
    goes on to the next layer, in the program alike (the model-configs
    guide, section 4): on one chip there is no exchange;
  * the router's product is float32 at `highest` in every `precision` (the
    program holds it so too); every other product takes the rounding of
    `precision` (`common._round_forward`, `_round_backward`).

Attention runs in blocks of queries (`lax.map`), each against all keys
under the mask, recomputed in the backward pass, and each layer is
recomputed in the backward pass: the same mathematics, and float32 at 8192
positions fits the chip beside the check's arrays. Experts run one after
the other over all tokens, each token's term weighted by `p_e` (zero where
the token was not routed to e).

Parameters are a flat {path: array} dict under the program's own paths;
nothing is read from the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from . import common as C

_QUERY_BLOCK = 256


def _plan(cfg: dict):
    """(path, shape, init std or None for ones) in order of use."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    held, width = cfg["num_experts"], cfg["moe_intermediate_size"]
    router, vocab = cfg["published"]["num_experts"], cfg["vocab_size"]
    std = 0.02
    # Unit embeddings (assumed): at 0.02 a layer's output swamps them at
    # random weights, from the second layer on every token's router input
    # shares one direction, and the held experts' load is 2.5 to 8 times
    # uneven and differs by seed; a trained model's stream carries the
    # token, as this one then does
    plan = [("embed/embedding", (vocab, h), 1.0)]
    for i in range(cfg["num_hidden_layers"]):
        p = f"layers_{i}/"
        plan += [(p + "input_norm/scale", (h,), None),
                 (p + "attn/q_proj/kernel", (h, hq, d), std),
                 (p + "attn/k_proj/kernel", (h, hkv, d), std),
                 (p + "attn/v_proj/kernel", (h, hkv, d), std),
                 (p + "attn/o_proj/kernel", (hq, d, h), std),
                 (p + "post_attn_norm/scale", (h,), None),
                 (p + "moe/router", (h, router), std),
                 (p + "moe/w1", (held, h, width), std),
                 (p + "moe/w3", (held, h, width), std),
                 (p + "moe/w2", (held, width, h), std)]
    return plan + [("norm/scale", (h,), None), ("lm_head", (h, vocab), std)]


def init_params(key, cfg: dict) -> dict:
    """Seeded weights: normal(0, 0.02) products (assumed: the family's
    convention), normal(0, 1) embedding, unit norm scales. Trace it under
    one `jax.jit`."""
    return {path: (jnp.ones(shape, jnp.float32) if std is None else
                   C.normal_init(jax.random.fold_in(key, i), shape, std))
            for i, (path, shape, std) in enumerate(_plan(cfg))}


def param_shapes(cfg: dict) -> dict:
    return {path: shape for path, shape, _ in _plan(cfg)}


def _product(spec: str, a, b, precision: str):
    """`einsum(spec, a, b)` with both operands and the cotangent held as
    `precision` holds them, accumulated in float32."""
    a, b = C._round_forward(a, precision), C._round_forward(b, precision)
    return C._round_backward(
        jnp.einsum(spec, a, b, precision=C.HIGHEST), precision)


def rms_norm(x, scale, eps: float):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def inv_frequencies(rope: dict, head_dim: int):
    """(inverse frequency of each of the head_dim / 2 pairs, the factor on
    cos and sin) for one entry of the config's `rope_parameters`."""
    theta = float(rope["rope_theta"])
    plain = [theta ** (-2.0 * i / head_dim) for i in range(head_dim // 2)]
    if rope["rope_type"] == "default":
        return plain, 1.0
    assert rope["rope_type"] == "yarn", rope
    factor = float(rope["factor"])
    original = rope["original_max_position_embeddings"]

    def pair_of(turns):     # the pair that turns `turns` times in `original`
        return (head_dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_of(rope["beta_fast"])), 0)
    high = min(math.ceil(pair_of(rope["beta_slow"])), head_dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i, f in enumerate(plain):
        slowed = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(f / factor * slowed + f * (1.0 - slowed))
    return out, float(rope["attention_factor"])


def rotate(x, inv_freq, scale: float):
    """x [B, S, heads, d]: `x * cos + rotate_half(x) * sin`, the angle of
    pair i at position s being `s * inv_freq[i]`, pairs (i, i + d/2)."""
    pos = jnp.arange(x.shape[1], dtype=jnp.float32)
    ang = pos[:, None] * jnp.asarray(inv_freq, jnp.float32)[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * (jnp.cos(ang) * scale) + turned * (jnp.sin(ang) * scale)


def attention(q, k, v, window, precision: str):
    """q [B, S, Hkv, G, d], k and v [B, S, Hkv, d] -> [B, S, Hkv, G, d];
    `window` None for a full layer."""
    b, s, hkv, g, d = q.shape
    block = min(_QUERY_BLOCK, s)
    assert s % block == 0
    keys = jnp.arange(s)

    @jax.checkpoint
    def one(i):
        q_i = lax.dynamic_slice_in_dim(q, i * block, block, axis=1)
        scores = _product("bqhgd,bkhd->bhgqk", q_i, k, precision) / math.sqrt(d)
        dist = (i * block + jnp.arange(block))[:, None] - keys[None, :]
        seen = dist >= 0
        if window is not None:
            seen = seen & (dist < window)
        scores = jnp.where(seen[None, None, None], scores, -jnp.inf)
        return _product("bhgqk,bkhd->bqhgd", jax.nn.softmax(scores, axis=-1),
                        v, precision)

    out = lax.map(one, jnp.arange(s // block))      # [blocks, B, block, ...]
    return jnp.moveaxis(out, 0, 1).reshape(b, s, hkv, g, d)


def experts(x, params, prefix: str, cfg: dict, precision: str):
    """x [T, h] -> the held experts' part of the layer's output."""
    held, top = cfg["num_experts"], cfg["num_experts_per_tok"]
    first = cfg["share"]["expert_share"] * held
    logits = jnp.dot(x, params[prefix + "router"], precision=C.HIGHEST)
    weights, chosen = lax.top_k(jax.nn.softmax(logits, axis=-1), top)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    @jax.checkpoint
    def add_expert(y, packed):
        e, w1, w3, w2 = packed
        p_e = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        gate = jax.nn.silu(_product("th,hw->tw", x, w1, precision))
        up = _product("th,hw->tw", x, w3, precision)
        return y + p_e[:, None] * _product("tw,wh->th", gate * up, w2,
                                           precision), None

    # one expert after the other, as a loop of the program and not of its
    # text (the gradient program compiles in half the time), each
    # recomputed in the backward pass (or the loop keeps all of theirs)
    y, _ = lax.scan(add_expert, jnp.zeros_like(x),
                    (jnp.arange(held), params[prefix + "w1"],
                     params[prefix + "w3"], params[prefix + "w2"]))
    return y


def loss(params: dict, batch, cfg: dict, precision: str = "float32"):
    """batch = (token ids i32 [B, S], next ids i32 [B, S], None)."""
    tokens, targets, _ = batch
    b, s = tokens.shape
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps = cfg["rms_norm_eps"]

    def layer(x, weights, kind):
        rope = cfg["rope_parameters"][kind]
        inv_freq, scale = inv_frequencies(rope, cfg["head_dim"])
        n = rms_norm(x, weights["input_norm/scale"], eps)
        q = _product("bsh,hnd->bsnd", n, weights["attn/q_proj/kernel"],
                     precision)
        k = _product("bsh,hnd->bsnd", n, weights["attn/k_proj/kernel"],
                     precision)
        v = _product("bsh,hnd->bsnd", n, weights["attn/v_proj/kernel"],
                     precision)
        q = rotate(q, inv_freq, scale).reshape(b, s, hkv, hq // hkv, -1)
        k = rotate(k, inv_freq, scale)
        window = (cfg["sliding_window"] if kind == "sliding_attention"
                  else None)
        a = attention(q, k, v, window, precision).reshape(b, s, hq, -1)
        x = x + _product("bsnd,ndh->bsh", a, weights["attn/o_proj/kernel"],
                         precision)
        n = rms_norm(x, weights["post_attn_norm/scale"], eps)
        y = experts(n.reshape(b * s, -1), weights, "moe/", cfg, precision)
        return x + y.reshape(x.shape)

    x = params["embed/embedding"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        prefix = f"layers_{i}/"
        weights = {p[len(prefix):]: v for p, v in params.items()
                   if p.startswith(prefix)}
        x = jax.checkpoint(layer, static_argnums=2)(
            x, weights, cfg["layer_types"][i])
    x = rms_norm(x, params["norm/scale"], eps)
    logits = _product("bsh,hv->bsv", x, params["lm_head"], precision)
    return C.cross_entropy(logits.reshape(b * s, -1), targets.reshape(-1))
