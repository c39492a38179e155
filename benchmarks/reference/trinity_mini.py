"""Plain reference of the `trinity_mini` configuration: Trinity-Mini
(arcee-ai/Trinity-Mini, config.json, `model_type` `afmoe`), one chip's
share of a group of chips that divide each layer by experts and by
vocabulary rows.

Every number is the configuration file's, under the published config's own
keys (`hidden_size`, `intermediate_size`, `moe_intermediate_size`,
`head_dim`, `num_attention_heads`, `num_key_value_heads`, `layer_types`,
`sliding_window`, `rope_theta`, `rms_norm_eps`, `num_experts_per_tok`,
`num_shared_experts`, `route_norm`, `route_scale`, `score_func`,
`mup_enabled`); `num_hidden_layers`, `num_dense_layers`, `num_experts` (the
experts HELD here) and `vocab_size` (the rows held) are the cut ones,
`published.num_experts` is the router's width, and `share` says which
experts are held (`expert_share` of `expert_shares`) and which of the
published layers (`layers`, indices into `layer_types`, the first
`num_dense_layers` of them with the dense MLP).

Per layer `i` (RMSNorm eps `rms_norm_eps`, no bias anywhere; `x` a token's
stream), as transformers' `modeling_afmoe.py` has it (the file's `assumed`
says what of it the published config has no key for):

    u = n_in(x)
    q = turn_i(norm(u W_q) g_q),  k = turn_i(norm(u W_k) g_k)
        (heads of `head_dim`, a norm over each head's entries)
    o = concat_h(softmax_mask_i(q_h k_j^T / sqrt(d)) v_j)
        (key/value head j serves a group of query heads)
    h = x + n_post_attn((sigmoid(u W_g) * o) W_o)
    y = h + n_post_mlp(mlp_i(n_pre_mlp(h)))

    sliding_attention:  turn = half-split rotary at `rope_theta`;
                        keys 0 <= i - j < `sliding_window`
    full_attention:     turn = identity (NO positions); all earlier keys
    mlp, dense:         W2 (silu(W1 x) * W3 x)
    mlp, experts:       s = sigmoid(x W_r) in float32;  C = top_k(s + b)
                        shared(x) + sum over held e in C of
                            (route_scale s_e / (sum_C s + 1e-20)) F_e(x)

The stream starts at `embedding[token] * sqrt(hidden_size)`
(`mup_enabled`); after the last layer a RMSNorm and an untied head.

Departures from the published description (the file's `assumed`):
  * `router_bias` (the published `expert_bias`) is a parameter handed in
    with the others; it takes part in the selection alone, so the loss has
    no gradient by it. The rule that moves it by the experts' load is not
    followed, and the balance loss (`load_balance_coeff`) is not in the
    objective;
  * what absent experts would add to `y` is left out, and the partial sum
    goes on to the next layer, in the program alike (the model-configs
    guide, section 4): on one chip there is no exchange;
  * the router's product is float32 at `highest` in every `precision` (the
    program holds it so too); every other product takes the rounding of
    `precision` (`common._round_forward`, `_round_backward`). Both
    sigmoids, the norms and the turn are elementwise, in float32 in every
    precision.

In blocks, so that float32 at 8192 positions and 25 024 rows fits the chip
beside the check's arrays: attention in blocks of queries (`lax.map`), each
against all keys under the mask; the head and the cross-entropy in blocks
of tokens; each of them and each layer recomputed in the backward pass. The
held experts run one after the other over all tokens, each token's term
weighted by its gate (zero where the token was not routed to the expert).

Parameters are a flat {path: array} dict under the program's own paths
(`layers_<i>/...`, i the layer's place among those held); nothing is read
from the program. A layer's kind is DATA here (`reach`, the keys a query
sees back, and `turned`, whether positions turn q and k), so the held
layers that follow one another and have the same leaves (the four with
experts) are stacked and run as one `lax.scan`, though the program unrolls
them and builds a window layer and a full layer differently: one layer's
text for the compiler, the same arithmetic. For the compiler's sake too the
products of one input are one product (`_products`). Device-less for a v5e
the gradient program compiles in 95 s with neither, 57 s with the products
joined and 43 s with both (PERF.md section 6, PR 40); a run has 360 s from
an empty compile cache to its result line.
"""

from __future__ import annotations

import itertools
import math

import jax
import jax.numpy as jnp
from jax import lax

from . import common as C

_QUERY_BLOCK = 256
_TOKEN_BLOCK = 2048     # tokens of the head and the loss at a time
_SUM_EPS = 1e-20        # in the chosen scores' sum (`route_norm`)
SLIDING, FULL = "sliding_attention", "full_attention"


def layers(cfg: dict) -> list:
    """(kind, dense) of each layer held here, in order."""
    held = cfg["share"]["layers"]
    assert len(held) == cfg["num_hidden_layers"]
    return [(cfg["layer_types"][i], at < cfg["num_dense_layers"])
            for at, i in enumerate(held)]


def _layer_plan(p: str, cfg: dict, dense: bool):
    h, d = cfg["hidden_size"], cfg["head_dim"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    std = 0.02
    plan = [(p + "input_norm/scale", (h,), None),
            (p + "attn/q_proj/kernel", (h, heads, d), std),
            (p + "attn/k_proj/kernel", (h, kv_heads, d), std),
            (p + "attn/v_proj/kernel", (h, kv_heads, d), std),
            (p + "attn/gate_proj/kernel", (h, heads, d), std),
            (p + "attn/q_layernorm", (d,), None),
            (p + "attn/k_layernorm", (d,), None),
            (p + "attn/o_proj/kernel", (heads, d, h), std),
            (p + "post_attn_norm/scale", (h,), None),
            (p + "pre_mlp_norm/scale", (h,), None)]
    if dense:
        wide = cfg["intermediate_size"]
        plan += [(p + "mlp/w1", (h, wide), std),
                 (p + "mlp/w3", (h, wide), std),
                 (p + "mlp/w2", (wide, h), std)]
    else:
        held, width = cfg["num_experts"], cfg["moe_intermediate_size"]
        router = cfg["published"]["num_experts"]
        shared = cfg["num_shared_experts"] * width
        plan += [(p + "moe/router", (h, router), std),
                 (p + "moe/router_bias", (router,), 0.0),
                 (p + "moe/w1", (held, h, width), std),
                 (p + "moe/w3", (held, h, width), std),
                 (p + "moe/w2", (held, width, h), std),
                 (p + "moe/shared/w1", (h, shared), std),
                 (p + "moe/shared/w3", (h, shared), std),
                 (p + "moe/shared/w2", (shared, h), std)]
    return plan + [(p + "post_mlp_norm/scale", (h,), None)]


def _plan(cfg: dict):
    """(path, shape, init std, None for ones, 0.0 for zeros) in order of
    use."""
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    # Unit embeddings (assumed), as the three sibling configurations': at
    # the products' 0.02 the stream starts at 0.9 an entry, every branch
    # leaves its norm at 1.0 an entry with most of it common to all
    # tokens, and the seeded router does not tell tokens apart
    plan = [("embed/embedding", (vocab, h), 1.0)]
    for i, (_, dense) in enumerate(layers(cfg)):
        plan += _layer_plan(f"layers_{i}/", cfg, dense)
    return plan + [("norm/scale", (h,), None), ("lm_head", (h, vocab), 0.02)]


def init_params(key, cfg: dict) -> dict:
    """Seeded weights: normal(0, 0.02) for every product (assumed: the
    family's `initializer_range`), normal(0, 1) embedding, unit norm scales,
    a zero selection bias. Trace it under one `jax.jit`. The normal leaves
    are cut from ONE draw of the generator the chip has in hardware, in the
    order of `_plan`."""
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


def _products(spec: str, a, bs, precision: str):
    """`[_product(spec, a, b, precision) for b in bs]` as ONE product of
    `a` with the `bs` side by side along their second axis: entry for entry
    the same sums, every operand and every cotangent still rounded on its
    own, and a `highest` product fewer for the compiler each time (this
    file's gradient program compiles in 57 s for four separate projections
    and two separate halves of every gated MLP, in 95 s without)."""
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


def attention(q, k, v, reach, precision: str):
    """q [B, S, H, d], k and v [B, S, Hkv, d] -> [B, S, H, d], scaled by
    1 / sqrt(d); query i sees keys `0 <= i - j < reach` (`reach` a number
    or a traced scalar; S or more: every earlier key); key/value head j
    serves query heads `j * H / Hkv` up to the next one's first. The
    queries are filled up to whole blocks with rows that are thrown away."""
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
        back = (i * block + jnp.arange(block))[:, None] - keys[None, :]
        seen = (back >= 0) & (back < reach)
        scores = jnp.where(seen[None, None, None], scores, -jnp.inf)
        return _product("bhgqk,bkhd->bqhgd", jax.nn.softmax(scores, axis=-1),
                        v, precision)

    out = lax.map(one, jnp.arange((s + fill) // block))
    return jnp.moveaxis(out, 0, 1).reshape(b, s + fill, heads, d)[:, :s]


def kind_as_data(cfg: dict, kind: str, positions: int):
    """(reach, turned) of a layer of `kind`: a window layer sees
    `sliding_window` keys back under rotary positions, a full layer all of
    them under none."""
    assert kind in (SLIDING, FULL)
    if kind == SLIDING:
        return cfg["sliding_window"], True
    return positions, False


def gated_attention(u, weights: dict, cfg: dict, precision: str, reach,
                    turned):
    """u [B, S, h] -> [B, S, h]: the attention branch between its two
    norms; `weights` under the module's own paths; `reach` and `turned` as
    `kind_as_data` gives them, numbers or traced scalars."""
    eps = cfg["rms_norm_eps"]
    q, k, v, gate = _products("bsh,hnd->bsnd", u, [
        weights[name + "_proj/kernel"] for name in ("q", "k", "v", "gate")],
        precision)
    q = rms_norm(q, weights["q_layernorm"], eps)
    k = rms_norm(k, weights["k_layernorm"], eps)
    assert cfg.get("rope_scaling") is None
    theta = float(cfg["rope_theta"])
    # a full layer has no positions: its q and k stay as they are
    q = jnp.where(turned, rotate_halves(q, theta), q)
    k = jnp.where(turned, rotate_halves(k, theta), k)
    out = attention(q, k, v, reach, precision)
    return _product("bsnd,ndh->bsh", jax.nn.sigmoid(gate) * out,
                    weights["o_proj/kernel"], precision)


def gated(x, w1, w3, w2, precision: str):
    gate, up = _products("th,hw->tw", x, [w1, w3], precision)
    return _product("tw,wh->th", jax.nn.silu(gate) * up, w2, precision)


def gates(x, router, bias, cfg: dict):
    """(each token's chosen experts [T, top], their gates [T, top]) over
    ALL the router's outputs."""
    top = cfg["num_experts_per_tok"]
    assert cfg.get("score_func", "sigmoid") == "sigmoid"
    scores = jax.nn.sigmoid(jnp.dot(x, router, precision=C.HIGHEST))
    _, chosen = lax.top_k(scores + bias, top)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.get("route_norm", True):
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + _SUM_EPS)
    return chosen, picked * cfg["route_scale"]


def experts(x, weights: dict, cfg: dict, precision: str):
    """x [T, h] -> the held experts' part of the layer's output, and the
    shared expert's."""
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
    return y + gated(x, weights["shared/w1"], weights["shared/w3"],
                     weights["shared/w2"], precision)


def _under(params: dict, prefix: str) -> dict:
    return {p[len(prefix):]: v for p, v in params.items()
            if p.startswith(prefix)}


def layer(x, weights: dict, cfg: dict, precision: str, kind, dense: bool):
    """x [B, S, h] through one layer; `weights` under the layer's own paths
    (the four norms' `.../scale`, `attn/...`, `mlp/...` or `moe/...`);
    `kind` its name or `kind_as_data`'s pair."""
    b, s, _ = x.shape
    eps = cfg["rms_norm_eps"]
    reach, turned = (kind_as_data(cfg, kind, s) if isinstance(kind, str)
                     else kind)
    u = rms_norm(x, weights["input_norm/scale"], eps)
    a = gated_attention(u, _under(weights, "attn/"), cfg, precision, reach,
                        turned)
    x = x + rms_norm(a, weights["post_attn_norm/scale"], eps)
    n = rms_norm(x, weights["pre_mlp_norm/scale"], eps).reshape(b * s, -1)
    if dense:
        f = gated(n, weights["mlp/w1"], weights["mlp/w3"], weights["mlp/w2"],
                  precision)
    else:
        f = experts(n, _under(weights, "moe/"), cfg, precision)
    return x + rms_norm(f.reshape(x.shape), weights["post_mlp_norm/scale"],
                        eps)


def _runs(dense: list) -> list:
    """[(first, count)] of the stretches of neighbours with the same
    leaves: with the dense MLP, or with experts."""
    out = []
    for i, kind in enumerate(dense):
        if out and dense[out[-1][0]] == kind:
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
        lambda x, w, kind, dense: layer(x, w, cfg, precision, kind, dense),
        static_argnums=3)
    x = params["embed/embedding"][tokens]
    if cfg.get("mup_enabled", False):
        x = x * math.sqrt(cfg["hidden_size"])
    for first, count in _runs([dense for _, dense in kinds]):
        dense = kinds[first][1]
        each = [_under(params, f"layers_{i}/")
                for i in range(first, first + count)]
        data = [kind_as_data(cfg, kind, tokens.shape[1])
                for kind, _ in kinds[first:first + count]]
        if count == 1:
            x = one(x, each[0], data[0], dense)
            continue
        # the same leaves and next to each other: stacked here, one after
        # the other as a loop of the program and not of its text
        stacked = {p: jnp.stack([w[p] for w in each]) for p in each[0]}
        reach = jnp.asarray([r for r, _ in data], jnp.int32)
        turned = jnp.asarray([t for _, t in data])
        x, _ = lax.scan(
            lambda x, w: (one(x, w[0], (w[1], w[2]), dense), None), x,
            (stacked, reach, turned))
    x = rms_norm(x, params["norm/scale"], cfg["rms_norm_eps"])
    return head_loss(x.reshape(-1, x.shape[-1]), params["lm_head"],
                     targets.reshape(-1), precision)
