"""Plain reference of the `lfm2_8b_a1b` configuration: LFM2-8B-A1B
(LiquidAI/LFM2-8B-A1B, config.json, `model_type` `lfm2_moe`), one chip's
share of a group of chips that divide each layer by experts and by
vocabulary rows.

Every number is the configuration file's, under the published config's own
keys (`hidden_size`, `intermediate_size`, `moe_intermediate_size`,
`conv_L_cache`, `conv_bias`, `layer_types`, `num_attention_heads`,
`num_key_value_heads`, `rope_theta`, `norm_eps`, `norm_topk_prob`,
`num_experts_per_tok`, `routed_scaling_factor`, `use_expert_bias`);
`num_hidden_layers`, `num_dense_layers`, `num_experts` (the experts HELD
here) and `vocab_size` (the rows held) are the cut ones,
`published.num_experts` is the router's width, and `share` says which
experts are held (`expert_share` of `expert_shares`) and which of the
published layers (`layers`, indices into `layer_types`, the first
`num_dense_layers` of them with the dense MLP).

Per layer (pre-norm, RMSNorm eps `norm_eps`, no bias anywhere; `h` a
token's stream), as transformers' `modeling_lfm2_moe.py` has it:

    h' = h + op(operator_norm(h));   y = h' + ffn(ffn_norm(h'))
    conv:            [B | C | x] = u W_in;  v = B * x
                     c_t = k_0 v_{t-2} + k_1 v_{t-1} + k_2 v_t  a channel,
                     zeros before the sequence starts
                     op = (C * c) W_out
    full_attention:  q, k, v = u W_q, u W_k, u W_v  (heads of `head_dim`)
                     q, k = rope(norm(q) g_q), rope(norm(k) g_k)
                     op = concat_i(softmax_causal(q_i k_j^T / sqrt(d)) v_j)
                          W_o       (key/value head j serves a group of
                                     query heads)
    ffn, dense:      W2 (silu(W1 x) * W3 x)
    ffn, experts:    s = sigmoid(x W_r) in float32;  C = top_k(s + b)
                     sum over held e in C of
                         (scale s_e / (sum_C s + 1e-6)) F_e(x)

then RMSNorm (`embedding_norm`) and the head, which is the embedding
transposed (`tie_embedding`): one leaf with gradient by both paths.

Departures from the published description (the file's `assumed`):
  * `router_bias` (the published `expert_bias`) is a parameter handed in
    with the others; it takes part in the selection alone, so the loss has
    no gradient by it. The rule that moves it by the experts' load is not
    followed: the config gives no speed for it, and it is a state outside
    the gradient;
  * what absent experts would add to `y` is left out, and the partial sum
    goes on to the next layer, in the program alike (the model-configs
    guide, section 4): on one chip there is no exchange;
  * the router's product is float32 at `highest` in every `precision` (the
    program holds it so too); every other product takes the rounding of
    `precision` (`common._round_forward`, `_round_backward`). The gates
    and the taps are elementwise, in float32 in every precision.

Attention runs in blocks of queries (`lax.map`), each against all keys
under the mask, recomputed in the backward pass, and each layer is
recomputed in the backward pass: the same mathematics, and float32 at 8192
positions fits the chip beside the check's arrays. The held experts run
one after the other over all tokens, each token's term weighted by its
gate (zero where the token was not routed to the expert).

Parameters are a flat {path: array} dict under the program's own paths
(`layers_<i>/...`, i the layer's place among those held); nothing is read
from the program. Held layers that follow one another and are alike (the
three `conv` layers with experts) are stacked HERE and run as one
`lax.scan`, though the program unrolls them: one layer's text for the
compiler, the same arithmetic.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from . import common as C

_QUERY_BLOCK = 256
_SUM_EPS = 1e-6         # in the chosen scores' sum (`norm_topk_prob`)
CONV, FULL = "conv", "full_attention"


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or (cfg["hidden_size"]
                                   // cfg["num_attention_heads"])


def layers(cfg: dict) -> list:
    """(kind, dense) of each layer held here, in order."""
    held = cfg["share"]["layers"]
    assert len(held) == cfg["num_hidden_layers"]
    return [(cfg["layer_types"][i], at < cfg["num_dense_layers"])
            for at, i in enumerate(held)]


def _layer_plan(p: str, cfg: dict, kind: str, dense: bool):
    h, d = cfg["hidden_size"], head_dim(cfg)
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    std = 0.02
    plan = [(p + "operator_norm/scale", (h,), None)]
    if kind == CONV:
        assert not cfg["conv_bias"]
        plan += [(p + "conv/in_proj/kernel", (h, 3 * h), std),
                 (p + "conv/taps", (h, cfg["conv_L_cache"]), std),
                 (p + "conv/out_proj/kernel", (h, h), std)]
    else:
        plan += [(p + "attn/q_proj/kernel", (h, heads, d), std),
                 (p + "attn/k_proj/kernel", (h, kv_heads, d), std),
                 (p + "attn/v_proj/kernel", (h, kv_heads, d), std),
                 (p + "attn/q_layernorm", (d,), None),
                 (p + "attn/k_layernorm", (d,), None),
                 (p + "attn/o_proj/kernel", (heads, d, h), std)]
    plan += [(p + "ffn_norm/scale", (h,), None)]
    if dense:
        wide = cfg["intermediate_size"]
        return plan + [(p + "mlp/w1", (h, wide), std),
                       (p + "mlp/w3", (h, wide), std),
                       (p + "mlp/w2", (wide, h), std)]
    held, width = cfg["num_experts"], cfg["moe_intermediate_size"]
    router = cfg["published"]["num_experts"]
    return plan + [(p + "moe/router", (h, router), std),
                   (p + "moe/router_bias", (router,), 0.0),
                   (p + "moe/w1", (held, h, width), std),
                   (p + "moe/w3", (held, h, width), std),
                   (p + "moe/w2", (held, width, h), std)]


def _plan(cfg: dict):
    """(path, shape, init std, None for ones, 0.0 for zeros) in order of
    use."""
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    # Unit embeddings (assumed), as `mellum2_12b_a2p5b`'s: at 0.02 a layer's
    # output swamps them at random weights and the seeded router does not
    # tell tokens apart. The leaf is the head too
    plan = [("embed/embedding", (vocab, h), 1.0)]
    for i, (kind, dense) in enumerate(layers(cfg)):
        plan += _layer_plan(f"layers_{i}/", cfg, kind, dense)
    return plan + [("embedding_norm/scale", (h,), None)]


def init_params(key, cfg: dict) -> dict:
    """Seeded weights: normal(0, 0.02) products and taps (assumed: the
    family's `initializer_range`), normal(0, 1) embedding, unit norm
    scales, a zero selection bias. Trace it under one `jax.jit`. The normal
    leaves are cut from ONE draw of the generator the chip has in hardware,
    in the order of `_plan`."""
    plan = _plan(cfg)
    sizes = [math.prod(shape) if std else 0 for _, shape, std in plan]
    if not jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.wrap_key_data(key)
    key = jax.random.wrap_key_data(
        jnp.tile(jax.random.key_data(key).reshape(-1)[:2], 2), impl="rbg")
    draw = jax.random.normal(key, (sum(sizes),), jnp.float32)
    out, at = {}, 0
    for (path, shape, std), size in zip(plan, sizes):
        if std is None:
            out[path] = jnp.ones(shape, jnp.float32)
        elif std == 0.0:
            out[path] = jnp.zeros(shape, jnp.float32)
        else:
            out[path] = std * draw[at:at + size].reshape(shape)
            at += size
    return out


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


def rotate_halves(x, theta: float):
    """x [B, S, heads, d]: entries (j, j + d/2) turned by the angle
    `s * theta ** (-2j / d)` at position s."""
    d = x.shape[-1]
    inv_freq = jnp.asarray([theta ** (-2.0 * j / d) for j in range(d // 2)],
                           jnp.float32)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    ang = jnp.tile(ang, 2)[None, :, None, :]
    # (x0, x1) -> (-x1, x0) in every pair
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(ang) + turned * jnp.sin(ang)


def short_conv(u, weights: dict, cfg: dict, precision: str):
    """u [B, S, h] -> [B, S, h]: the double-gated causal depthwise
    convolution; `weights` under the mixer's own paths."""
    taps = cfg["conv_L_cache"]
    bcx = _product("bsh,hw->bsw", u, weights["in_proj/kernel"], precision)
    gate_in, gate_out, x = jnp.split(bcx, 3, axis=-1)
    v = gate_in * x
    s = v.shape[1]
    before = jnp.pad(v, ((0, 0), (taps - 1, 0), (0, 0)))
    # tap j meets the value `taps - 1 - j` positions back
    c = sum(weights["taps"][:, j] * before[:, j:j + s] for j in range(taps))
    return _product("bsh,hw->bsw", gate_out * c, weights["out_proj/kernel"],
                    precision)


def attention(q, k, v, precision: str):
    """q [B, S, H, d], k and v [B, S, Hkv, d] -> [B, S, H, d], causal,
    scaled by 1 / sqrt(d); key/value head j serves query heads
    `j * H / Hkv` up to the next one's first. The queries are filled up to
    whole blocks with rows that are thrown away."""
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


def attention_mixer(u, weights: dict, cfg: dict, precision: str):
    eps, theta = cfg["norm_eps"], float(cfg["rope_theta"])
    q = _product("bsh,hnd->bsnd", u, weights["q_proj/kernel"], precision)
    k = _product("bsh,hnd->bsnd", u, weights["k_proj/kernel"], precision)
    v = _product("bsh,hnd->bsnd", u, weights["v_proj/kernel"], precision)
    q = rotate_halves(rms_norm(q, weights["q_layernorm"], eps), theta)
    k = rotate_halves(rms_norm(k, weights["k_layernorm"], eps), theta)
    return _product("bsnd,ndh->bsh", attention(q, k, v, precision),
                    weights["o_proj/kernel"], precision)


def gated(x, w1, w3, w2, precision: str):
    gate = jax.nn.silu(_product("th,hw->tw", x, w1, precision))
    return _product("tw,wh->th", gate * _product("th,hw->tw", x, w3,
                                                 precision), w2, precision)


def gates(x, router, bias, cfg: dict):
    """(each token's chosen experts [T, top], their gates [T, top]) over
    ALL the router's outputs."""
    top = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(jnp.dot(x, router, precision=C.HIGHEST))
    by = scores + bias if cfg.get("use_expert_bias", True) else scores
    _, chosen = lax.top_k(by, top)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.get("norm_topk_prob", True):
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + _SUM_EPS)
    return chosen, picked * cfg["routed_scaling_factor"]


def experts(x, weights: dict, cfg: dict, precision: str):
    """x [T, h] -> the held experts' part of the layer's output."""
    held = cfg["num_experts"]
    first = cfg["share"]["expert_share"] * held
    chosen, weight = gates(x, weights["router"], weights["router_bias"], cfg)

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
    return y


def _under(params: dict, prefix: str) -> dict:
    return {p[len(prefix):]: v for p, v in params.items()
            if p.startswith(prefix)}


def layer(x, weights: dict, cfg: dict, precision: str, kind: str,
          dense: bool):
    """x [B, S, h] through one layer; `weights` under the layer's own paths
    (`operator_norm/scale`, `conv/...` or `attn/...`, `ffn_norm/scale`,
    `mlp/...` or `moe/...`)."""
    b, s, _ = x.shape
    eps = cfg["norm_eps"]
    u = rms_norm(x, weights["operator_norm/scale"], eps)
    if kind == CONV:
        x = x + short_conv(u, _under(weights, "conv/"), cfg, precision)
    else:
        x = x + attention_mixer(u, _under(weights, "attn/"), cfg, precision)
    n = rms_norm(x, weights["ffn_norm/scale"], eps).reshape(b * s, -1)
    if dense:
        y = gated(n, weights["mlp/w1"], weights["mlp/w3"], weights["mlp/w2"],
                  precision)
    else:
        y = experts(n, _under(weights, "moe/"), cfg, precision)
    return x + y.reshape(x.shape)


def _runs(kinds: list) -> list:
    """[(first, count)] of the stretches of equal neighbours."""
    out = []
    for i, kind in enumerate(kinds):
        if out and kinds[out[-1][0]] == kind:
            out[-1][1] += 1
        else:
            out.append([i, 1])
    return [tuple(r) for r in out]


def loss(params: dict, batch, cfg: dict, precision: str = "float32"):
    """batch = (token ids i32 [B, S], next ids i32 [B, S], None)."""
    tokens, targets, _ = batch
    assert cfg.get("tie_embedding", True)
    kinds = layers(cfg)
    # each layer is recomputed in the backward pass
    one = jax.checkpoint(
        lambda x, w, kind, dense: layer(x, w, cfg, precision, kind, dense),
        static_argnums=(2, 3))
    x = params["embed/embedding"][tokens]
    for first, count in _runs(kinds):
        kind, dense = kinds[first]
        each = [_under(params, f"layers_{i}/")
                for i in range(first, first + count)]
        if count == 1:
            x = one(x, each[0], kind, dense)
            continue
        # alike and next to each other: stacked here, one after the other
        # as a loop of the program and not of its text
        stacked = {p: jnp.stack([w[p] for w in each]) for p in each[0]}
        x, _ = lax.scan(lambda x, w: (one(x, w, kind, dense), None), x,
                        stacked)
    x = rms_norm(x, params["embedding_norm/scale"], cfg["norm_eps"])
    logits = _product("bsh,vh->bsv", x, params["embed/embedding"], precision)
    return C.cross_entropy(logits.reshape(-1, logits.shape[-1]),
                           targets.reshape(-1))
