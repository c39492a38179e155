"""Plain reference of the `joyai_llm_flash` configuration: JoyAI-LLM-Flash
(jdopensource/JoyAI-LLM-Flash, config.json, `model_type` `joyai_llm_flash`),
one chip's share of a group of chips that divide each layer by experts and
by vocabulary rows.

Every number is the configuration file's, under the published config's own
keys (`hidden_size`, `num_attention_heads`, `q_lora_rank`, `kv_lora_rank`,
`qk_nope_head_dim`, `qk_rope_head_dim`, `v_head_dim`, `rope_theta`,
`intermediate_size`, `moe_intermediate_size`, `num_experts_per_tok`,
`n_shared_experts`, `routed_scaling_factor`, `first_k_dense_replace`,
`rms_norm_eps`); `num_hidden_layers`, `n_routed_experts` (the experts HELD
here), `vocab_size` (the rows held) and `num_nextn_predict_layers` are the
cut ones, `published.n_routed_experts` is the router's width and `share`
says which experts are held (`expert_share` of `expert_shares`).

Per layer (pre-norm, RMSNorm eps `rms_norm_eps`, no bias anywhere; `h` a
token's stream):

    cq = norm(n1(h) Wqa);  [qn | qr] = cq Wqb                    per head
    [ckv | kr] = n1(h) Wkva;  ckv = norm(ckv);  [kn | v] = ckv Wkvb
    q_i = [qn_i | rope(qr_i)],  k_i = [kn_i | rope(kr)]  (kr one for all
          heads)
    h' = h + concat_i(softmax_causal(q_i k_i^T / sqrt(dn + dr)) v_i) Wo
    s = sigmoid(n2(h') Wr) in float32;  C = top_k(s + b)
    y = h' + sum over held e in C of (scale s_e / sum_C s) F_e(n2(h'))
           + F_shared(n2(h'))
    the first `first_k_dense_replace` layers:  y = h' + F_dense(n2(h'))
    F = W2 (silu(W1 x) * W3 x)

and, where `num_nextn_predict_layers` is 1, the multi-token-prediction
module over positions t = 0 .. S - 2 (DeepSeek-V3, arXiv:2412.19437, section
2.2, whose config keys this model carries):

    u_t = Wm [norm_e(embed(x_{t+1})) | norm_h(z_t)]     z: the last layer's
    one more layer of the second kind, the module's own    output before the
    final norm, the SHARED head                            final norm
    loss = CE(main, x_{t+1}) + mtp_lambda CE(module, x_{t+2})

Departures from the published description (the file's `assumed`):
  * `rope_interleave`: the rotary part's pair j is its entries (2j, 2j + 1),
    turned in place. transformers' `apply_rotary_pos_emb_interleave` first
    moves the even entries to the front half of q and of k alike and turns
    pair (j, j + d/2); q k^T is the same, since both are permuted alike;
  * `router_bias` (`e_score_correction_bias`) is a parameter handed in with
    the others; it takes part in the selection alone, so the loss has no
    gradient by it. The rule that moves it by the experts' load
    (`topk_method` `noaux_tc`) is not followed: the config gives no speed
    for it, and it is a state outside the gradient;
  * `n_group` 1, `topk_group` 1: one group, so the group-limited selection
    is the plain one;
  * `mtp_lambda`: the config gives no weight; the file's is DeepSeek-V3's;
  * what absent experts would add to `y` is left out, and the partial sum
    goes on to the next layer, in the program alike (the model-configs
    guide, section 4): on one chip there is no exchange. The shared expert
    is whole on every chip;
  * the router's product is float32 at `highest` in every `precision` (the
    program holds it so too); every other product takes the rounding of
    `precision` (`common._round_forward`, `_round_backward`).

Attention runs in blocks of queries (`lax.map`), each against all keys
under the mask, recomputed in the backward pass, and each layer is
recomputed in the backward pass: the same mathematics, and float32 at 8192
positions fits the chip beside the check's arrays. The held experts run
one after the other over all tokens, each token's term weighted by its
gate (zero where the token was not routed to the expert).

Parameters are a flat {path: array} dict under the program's own paths;
nothing is read from the program. The leading dense layers are
`layers_<i>/...`; the layers with experts are equal and the program holds
their weights stacked along a leading axis (`expert_layers/...`,
`[layers, ...]`), so they run here as a `lax.scan` over that axis too: one
layer's text, and one layer for the compiler.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from . import common as C

_QUERY_BLOCK = 256


def _layer_plan(p: str, cfg: dict, dense: bool):
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rot = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    q_rank, kv_rank, dv = (cfg["q_lora_rank"], cfg["kv_lora_rank"],
                           cfg["v_head_dim"])
    std = 0.02
    plan = [(p + "input_norm/scale", (h,), None),
            (p + "attn/q_a_proj/kernel", (h, q_rank), std),
            (p + "attn/q_a_norm/scale", (q_rank,), None),
            (p + "attn/q_b_proj/kernel", (q_rank, heads, nope + rot), std),
            (p + "attn/kv_a_proj/kernel", (h, kv_rank + rot), std),
            (p + "attn/kv_a_norm/scale", (kv_rank,), None),
            (p + "attn/kv_b_proj/kernel", (kv_rank, heads, nope + dv), std),
            (p + "attn/o_proj/kernel", (heads, dv, h), std),
            (p + "post_attn_norm/scale", (h,), None)]
    if dense:
        wide = cfg["intermediate_size"]
        return plan + [(p + "mlp/w1", (h, wide), std),
                       (p + "mlp/w3", (h, wide), std),
                       (p + "mlp/w2", (wide, h), std)]
    held, width = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    router = cfg["published"]["n_routed_experts"]
    shared = cfg["n_shared_experts"] * width
    return plan + [(p + "moe/router", (h, router), std),
                   (p + "moe/router_bias", (router,), 0.0),
                   (p + "moe/w1", (held, h, width), std),
                   (p + "moe/w3", (held, h, width), std),
                   (p + "moe/w2", (held, width, h), std),
                   (p + "moe/shared/w1", (h, shared), std),
                   (p + "moe/shared/w3", (h, shared), std),
                   (p + "moe/shared/w2", (shared, h), std)]


def _plan(cfg: dict):
    """(path, shape, init std, None for ones, 0.0 for zeros) in order of
    use."""
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    # Unit embeddings (assumed), as `mellum2_12b_a2p5b`'s: at 0.02 a layer's
    # output swamps them at random weights and the seeded router does not
    # tell tokens apart
    plan = [("embed/embedding", (vocab, h), 1.0)]
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    for i in range(dense):
        plan += _layer_plan(f"layers_{i}/", cfg, True)
    # the layers with experts are equal: their weights lie stacked along a
    # leading axis, one leaf a kind, as the program holds them
    stacked = cfg["num_hidden_layers"] - dense
    if stacked:
        plan += [(path, (stacked,) + shape, std) for path, shape, std
                 in _layer_plan("expert_layers/", cfg, False)]
    plan += [("norm/scale", (h,), None), ("lm_head", (h, vocab), 0.02)]
    if cfg["num_nextn_predict_layers"]:
        plan += [("mtp_embed_norm/scale", (h,), None),
                 ("mtp_hidden_norm/scale", (h,), None),
                 ("mtp_proj/kernel", (2 * h, h), 0.02)]
        plan += _layer_plan("mtp_block/", cfg, False)
        plan += [("mtp_norm/scale", (h,), None)]
    return plan


def init_params(key, cfg: dict) -> dict:
    """Seeded weights: normal(0, 0.02) products (assumed: the family's
    `initializer_range`), normal(0, 1) embedding, unit norm scales, a zero
    selection bias. Trace it under one `jax.jit`. The normal leaves are
    cut from ONE draw in the order of `_plan` (a draw a leaf compiles for
    half a minute on the chip at 83 leaves)."""
    plan = _plan(cfg)
    sizes = [math.prod(shape) if std else 0 for _, shape, std in plan]
    if not jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.wrap_key_data(key)
    # the generator the chip has in hardware (same seed, same weights, on
    # one backend): the default's arithmetic over 414 M entries is a third
    # of the compile
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


def rotate_adjacent(x, theta: float):
    """x [B, S, heads, d]: entries (2j, 2j + 1) turned by the angle
    `s * theta ** (-2j / d)` at position s."""
    d = x.shape[-1]
    inv_freq = jnp.asarray([theta ** (-2.0 * j / d) for j in range(d // 2)],
                           jnp.float32)
    pos = jnp.arange(x.shape[1], dtype=jnp.float32)
    ang = jnp.repeat(pos[:, None] * inv_freq[None, :], 2, axis=-1)
    ang = ang[None, :, None, :]
    # (x0, x1) -> (-x1, x0) in every pair
    turned = jnp.stack([-x[..., 1::2], x[..., 0::2]], axis=-1).reshape(x.shape)
    return x * jnp.cos(ang) + turned * jnp.sin(ang)


def attention(q, k, v, precision: str):
    """q and k [B, S, H, dqk], v [B, S, H, dv] -> [B, S, H, dv], causal,
    scaled by 1 / sqrt(dqk). The queries are filled up to whole blocks
    with rows that are thrown away."""
    b, s, heads, dqk = q.shape
    block = min(_QUERY_BLOCK, s)
    fill = -s % block
    q = jnp.pad(q, ((0, 0), (0, fill), (0, 0), (0, 0)))
    keys = jnp.arange(s)

    @jax.checkpoint
    def one(i):
        q_i = lax.dynamic_slice_in_dim(q, i * block, block, axis=1)
        scores = _product("bqhd,bkhd->bhqk", q_i, k, precision) / math.sqrt(
            dqk)
        seen = (i * block + jnp.arange(block))[:, None] >= keys[None, :]
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return _product("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1),
                        v, precision)

    out = lax.map(one, jnp.arange((s + fill) // block))
    return jnp.moveaxis(out, 0, 1).reshape(b, s + fill, heads, -1)[:, :s]


def gated(x, w1, w3, w2, precision: str):
    gate = jax.nn.silu(_product("th,hw->tw", x, w1, precision))
    return _product("tw,wh->th", gate * _product("th,hw->tw", x, w3,
                                                 precision), w2, precision)


def gates(x, router, bias, cfg: dict):
    """(each token's chosen experts [T, top], their gates [T, top]) over
    ALL the router's outputs."""
    top = cfg["num_experts_per_tok"]
    assert cfg.get("scoring_func", "sigmoid") == "sigmoid"
    scores = jax.nn.sigmoid(jnp.dot(x, router, precision=C.HIGHEST))
    _, chosen = lax.top_k(scores + bias, top)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.get("norm_topk_prob", True):
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return chosen, picked * cfg["routed_scaling_factor"]


def experts(x, params, prefix: str, cfg: dict, precision: str):
    """x [T, h] -> the held experts' part of the layer's output, and the
    shared expert's."""
    held = cfg["n_routed_experts"]
    first = cfg["share"]["expert_share"] * held
    chosen, weights = gates(x, params[prefix + "router"],
                            params[prefix + "router_bias"], cfg)

    @jax.checkpoint
    def add_expert(y, packed):
        e, w1, w3, w2 = packed
        g_e = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        return y + g_e[:, None] * gated(x, w1, w3, w2, precision), None

    # one expert after the other, as a loop of the program and not of its
    # text, each recomputed in the backward pass
    y, _ = lax.scan(add_expert, jnp.zeros_like(x),
                    (jnp.arange(held), params[prefix + "w1"],
                     params[prefix + "w3"], params[prefix + "w2"]))
    return y + gated(x, params[prefix + "shared/w1"],
                     params[prefix + "shared/w3"],
                     params[prefix + "shared/w2"], precision)


def layer(x, weights: dict, cfg: dict, precision: str, dense: bool):
    """x [B, S, h] through one layer; `weights` under the layer's own
    paths (`input_norm/scale`, `attn/...`, `mlp/...` or `moe/...`)."""
    b, s, _ = x.shape
    eps, nope = cfg["rms_norm_eps"], cfg["qk_nope_head_dim"]
    rank, heads = cfg["kv_lora_rank"], cfg["num_attention_heads"]
    n = rms_norm(x, weights["input_norm/scale"], eps)
    cq = rms_norm(_product("bsh,hr->bsr", n, weights["attn/q_a_proj/kernel"],
                           precision), weights["attn/q_a_norm/scale"], eps)
    q = _product("bsr,rnd->bsnd", cq, weights["attn/q_b_proj/kernel"],
                 precision)
    kva = _product("bsh,hr->bsr", n, weights["attn/kv_a_proj/kernel"],
                   precision)
    ckv = rms_norm(kva[..., :rank], weights["attn/kv_a_norm/scale"], eps)
    kv = _product("bsr,rnd->bsnd", ckv, weights["attn/kv_b_proj/kernel"],
                  precision)
    theta = float(cfg["rope_theta"])
    assert cfg["rope_interleave"] and cfg.get("rope_scaling") is None
    q = jnp.concatenate([q[..., :nope], rotate_adjacent(q[..., nope:], theta)],
                        axis=-1)
    k_rot = rotate_adjacent(kva[:, :, None, rank:], theta)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_rot, (b, s, heads, k_rot.shape[-1]))], axis=-1)
    a = attention(q, k, kv[..., nope:], precision)
    x = x + _product("bsnd,ndh->bsh", a, weights["attn/o_proj/kernel"],
                     precision)
    n = rms_norm(x, weights["post_attn_norm/scale"], eps).reshape(b * s, -1)
    if dense:
        y = gated(n, weights["mlp/w1"], weights["mlp/w3"], weights["mlp/w2"],
                  precision)
    else:
        y = experts(n, weights, "moe/", cfg, precision)
    return x + y.reshape(x.shape)


def _under(params: dict, prefix: str) -> dict:
    return {p[len(prefix):]: v for p, v in params.items()
            if p.startswith(prefix)}


def loss(params: dict, batch, cfg: dict, precision: str = "float32"):
    """batch = (token ids i32 [B, S], next ids i32 [B, S], None)."""
    tokens, targets, _ = batch
    eps = cfg["rms_norm_eps"]
    # each layer is recomputed in the backward pass
    one = jax.checkpoint(
        lambda x, w, dense: layer(x, w, cfg, precision, dense),
        static_argnums=2)

    def head(x, norm):
        logits = _product("bsh,hv->bsv", rms_norm(x, params[norm], eps),
                          params["lm_head"], precision)
        return logits.reshape(-1, logits.shape[-1])

    x = params["embed/embedding"][tokens]
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    for i in range(dense):
        x = one(x, _under(params, f"layers_{i}/"), True)
    if cfg["num_hidden_layers"] > dense:
        # one layer after the other over the stacked weights, as a loop of
        # the program and not of its text
        x, _ = lax.scan(lambda x, w: (one(x, w, False), None), x,
                        _under(params, "expert_layers/"))
    total = C.cross_entropy(head(x, "norm/scale"), targets.reshape(-1))
    if cfg["num_nextn_predict_layers"]:
        assert cfg["num_nextn_predict_layers"] == 1
        ahead = rms_norm(params["embed/embedding"][targets[:, :-1]],
                         params["mtp_embed_norm/scale"], eps)
        behind = rms_norm(x[:, :-1], params["mtp_hidden_norm/scale"], eps)
        u = _product("bsh,hd->bsd", jnp.concatenate([ahead, behind], axis=-1),
                     params["mtp_proj/kernel"], precision)
        u = one(u, _under(params, "mtp_block/"), False)
        total = total + cfg["mtp_lambda"] * C.cross_entropy(
            head(u, "mtp_norm/scale"), targets[:, 1:].reshape(-1))
    return total
