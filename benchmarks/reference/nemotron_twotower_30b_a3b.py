"""Plain reference of the `nemotron_twotower_30b_a3b` configuration: the
CAUSAL tower of Nemotron-Labs-TwoTower-30B-A3B
(nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16, config.json, `model_type`
`nemotron_h`), one chip's share of a group of chips that divide each block
by experts and by vocabulary rows. The model's second, denoising tower and
its diffusion objective are left out (the file's `assumed`): the catalogued
config has no key of theirs.

Every number is the configuration file's, under the published config's own
keys (`hidden_size`, `mamba_num_heads`, `mamba_head_dim`, `ssm_state_size`,
`n_groups`, `conv_kernel`, `use_conv_bias`, `layer_norm_epsilon`,
`time_step_min`, `time_step_max`, `time_step_floor`, `head_dim`,
`num_attention_heads`, `num_key_value_heads`, `moe_intermediate_size`,
`moe_shared_expert_intermediate_size`, `num_experts_per_tok`,
`norm_topk_prob`, `routed_scaling_factor`); `hybrid_override_pattern` (the
blocks held, a letter each), `num_hidden_layers` (their number),
`n_routed_experts` (the experts HELD here) and `vocab_size` (the rows held)
are the cut ones, `published.n_routed_experts` is the router's width,
`published.num_hidden_layers` the depth whose square root divides every
product that writes the stream at init (a mixer's `out_proj`, the experts'
and the shared expert's `w2`, the attention's `o_proj`), and `share` says
which experts are held (`expert_share` of `expert_shares`).

A block is ONE module under one norm (plain RMSNorm, eps
`layer_norm_epsilon`; `x` a token's stream, `u = norm(x)`), as transformers'
`Mamba2Mixer.torch_forward` and `Zamba2RMSNormGated` have the mixer:

    x <- x + module(u),  the module by the pattern's letter

    M:  [z | xBC | dt] = u W_in
        xBC_t <- silu(sum_{j < L} w_j xBC_{t-(L-1)+j} + bias)
            (a depthwise causal convolution, zeros before the sequence)
        [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
        per head h of group g = h // (heads / groups), S_0 = 0 [N, P]:
            S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t^T
            y_t = S_t^T C_t + D x_t             TOKEN BY TOKEN (`ssm_scan`)
        v = y silu(z);  module = (v / rms_group(v) * w_n) W_out
            (the root mean square over each group's columns)
    E:  s = sigmoid(u W_r) in float32 over all the router's outputs;
        C = the `num_experts_per_tok` largest of s + bias
        shared(u) + sum over held e in C of
            (routed_scaling_factor s_e / sum_C s) F_e(u),
        F = W2 relu(W1 .)^2   (the shared expert's form too)
    *:  q, k, v = u W_q, u W_k, u W_v; NOT turned (no positions)
        module = concat_h(softmax_causal(q_h k_j^T / sqrt(d)) v_j) W_o

After the last block a norm and an untied head.

Departures from the published description (the file's `assumed`):
  * what absent experts would add is left out, and the partial sum goes on
    to the next block, in the program alike (the model-configs guide,
    section 4): on one chip there is no exchange;
  * the router's product is float32 at `highest` in every `precision` (the
    program holds it so too); every other product takes the rounding of
    `precision` (`common._round_forward`, `_round_backward`). The scan's own
    sums (`S^T C`) are float32 multiply-adds and no matrix product; sigmoids,
    softplus, decays, norms and the taps are elementwise, float32 in every
    precision.

So that float32 at 8192 positions fits the chip beside the check's arrays:
the state-space mixer one sequence after the other; its scan in stretches
of `_SCAN_BLOCK` tokens, each recomputed in the backward pass, with `B` and
`C` as their groups have them (never repeated for the heads); attention in blocks of queries (`lax.map`), each against all
keys under the mask; the head and the cross-entropy in blocks of tokens;
each block recomputed in the backward pass. The held experts run one after the other over all tokens,
each token's term weighted by its gate (zero where the token was not routed
to the expert).

Parameters are a flat {path: array} dict under the program's own paths
(`blocks_<i>/...`, i the block's place among those held); nothing is read
from the program. The blocks are unrolled, as the program's: stacked and
scanned (`EM` three times) the gradient program compiled no faster (37-45 s
for 42, device-less for a v5e) and held the stacked weights and their
stacked gradient beside the arguments and the result, 13.3 GB of temporaries
for 8.9 (PERF.md section 6, PR 48).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.scipy.special import ndtr

from . import common as C

_QUERY_BLOCK = 256
_TOKEN_BLOCK = 2048     # tokens of the head and the loss at a time
_SCAN_BLOCK = 64        # tokens of the scan between two kept states
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def blocks(cfg: dict) -> str:
    """The kind of each block held here, in order."""
    pattern = cfg["hybrid_override_pattern"]
    assert len(pattern) == cfg["num_hidden_layers"]
    assert not set(pattern) - {MAMBA, EXPERTS, ATTENTION}
    return pattern


def _widths(cfg: dict):
    """(heads, a head's size, the state's size, groups)."""
    return (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
            cfg["ssm_state_size"], cfg["n_groups"])


def _block_plan(p: str, cfg: dict, kind: str):
    h, std = cfg["hidden_size"], 0.02
    # `rescale_prenorm_residual`: what writes the stream, over the square
    # root of the PUBLISHED depth
    out = std / math.sqrt(cfg["published"]["num_hidden_layers"])
    plan = [(p + "norm/scale", (h,), None)]
    if kind == MAMBA:
        heads, size, state, groups = _widths(cfg)
        inner, mixed = heads * size, heads * size + 2 * groups * state
        assert cfg["use_conv_bias"] and not cfg["mamba_proj_bias"]
        m = p + "mixer/"
        return plan + [
            (m + "in_proj/kernel", (h, inner + mixed + heads), std),
            (m + "conv_taps", (mixed, cfg["conv_kernel"]), "uniform_taps"),
            (m + "conv_bias", (mixed,), 0.0),
            (m + "A_log", (heads,), "log_heads"),
            (m + "dt_bias", (heads,), "dt_bias"),
            (m + "D", (heads,), None),
            (m + "norm_scale", (inner,), None),
            (m + "out_proj/kernel", (inner, h), out)]
    if kind == ATTENTION:
        d = cfg["head_dim"]
        heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        a = p + "attn/"
        return plan + [(a + "q_proj/kernel", (h, heads, d), std),
                       (a + "k_proj/kernel", (h, kv, d), std),
                       (a + "v_proj/kernel", (h, kv, d), std),
                       (a + "o_proj/kernel", (heads, d, h), out)]
    held, width = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    shared = cfg["moe_shared_expert_intermediate_size"]
    r = p + "moe/"
    return plan + [
        (r + "router", (h, cfg["published"]["n_routed_experts"]), std),
        (r + "router_bias", (cfg["published"]["n_routed_experts"],), 0.0),
        (r + "w1", (held, h, width), std),
        (r + "w2", (held, width, h), out),
        (r + "shared/w1", (h, shared), std),
        (r + "shared/w2", (shared, h), out)]


def _plan(cfg: dict):
    """(path, shape, how it starts: a normal draw's deviation, None for
    ones, 0.0 for zeros, "uniform_taps" for uniform(-1, 1) / sqrt(taps),
    "dt_bias" for the inverse softplus of a draw uniform in its logarithm
    between `time_step_min` and `time_step_max`, "log_heads" for log(1),
    log(2), ...) in order of use."""
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    # Unit embeddings (assumed), as the sibling configurations': at the
    # products' 0.02 the seeded router does not tell tokens apart
    plan = [("embed/embedding", (vocab, h), 1.0)]
    for i, kind in enumerate(blocks(cfg)):
        plan += _block_plan(f"blocks_{i}/", cfg, kind)
    return plan + [("norm/scale", (h,), None), ("lm_head", (h, vocab), 0.02)]


def _drawn(how) -> bool:
    """Whether a leaf that starts as `how` is cut from the normal draw."""
    return how in ("uniform_taps", "dt_bias") or (
        isinstance(how, float) and how != 0.0)


def init_params(key, cfg: dict) -> dict:
    """Seeded weights (the file's `assumed.init`): normal(0, 0.02) for every
    product (those that write the stream over the square root of the
    published depth), normal(0, 1) embedding, unit norms and `D`, zero
    convolution and router biases, the taps uniform as torch draws a
    depthwise `Conv1d`, `A_log = log(1..heads)`, `dt_bias` the inverse
    softplus of a draw uniform in its logarithm. Trace it under one
    `jax.jit`. Every drawn leaf is cut from ONE normal draw of the generator
    the chip has in hardware, in the order of `_plan`; a uniform is the
    normal's own distribution function of it."""
    plan = _plan(cfg)
    sizes = [math.prod(shape) if _drawn(how) else 0
             for _, shape, how in plan]
    if not jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.wrap_key_data(key)
    key = jax.random.wrap_key_data(
        jnp.tile(jax.random.key_data(key).reshape(-1)[:2], 2), impl="rbg")
    draw = jax.random.normal(key, (sum(sizes),), jnp.float32)
    out, at = {}, 0
    for (path, shape, how), size in zip(plan, sizes):
        if not size:
            out[path] = (jnp.log(jnp.arange(1, shape[0] + 1,
                                            dtype=jnp.float32))
                         if how == "log_heads"
                         else jnp.full(shape, 1.0 if how is None else 0.0,
                                       jnp.float32))
            continue
        cut = draw[at:at + size]
        at += size
        # a leaf with few columns (the taps' 4) is cut as its transpose: the
        # TPU compiler moves such a reshape before the slice, and the WHOLE
        # draw as `[n / 4, 4]` is laid out a row a tile of 128 lanes
        cut = (cut.reshape(shape[::-1]).T if len(shape) == 2
               and shape[1] < 128 else cut.reshape(shape))
        if how == "uniform_taps":
            out[path] = (2.0 * ndtr(cut) - 1.0) / math.sqrt(shape[1])
        elif how == "dt_bias":
            low, high = (math.log(cfg[k]) for k in ("time_step_min",
                                                    "time_step_max"))
            dt = jnp.maximum(jnp.exp(low + (high - low) * ndtr(cut)),
                             cfg["time_step_floor"])
            out[path] = dt + jnp.log(-jnp.expm1(-dt))
        else:
            out[path] = how * cut
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


def causal_taps(x, taps, bias):
    """x [B, S, w], taps [w, L], bias [w]: `sum_j taps[:, j] x_{t-(L-1)+j} +
    bias`, zeros before the sequence."""
    s, length = x.shape[1], taps.shape[1]
    filled = jnp.pad(x, ((0, 0), (length - 1, 0), (0, 0)))
    return sum(taps[:, j] * filled[:, j:j + s] for j in range(length)) + bias


def ssm_scan(x, dt, a, b_in, c_in):
    """The state-space recurrence, token by token. x [B, S, G, R, P] (R
    heads to each of G groups), dt [B, S, G, R], a [G, R], b_in and c_in [B,
    S, G, N] (a group's, which its heads share) -> y [B, S, G, R, P] without
    the `D x` term. `S` [B, G, R, N, P] starts at zero; the sum `S^T C` is
    float32 multiply-adds."""
    b, s, g, r, p = x.shape
    block = _SCAN_BLOCK if s % _SCAN_BLOCK == 0 else s

    def token(state, at):
        x_t, dt_t, b_t, c_t = at
        state = (state * jnp.exp(dt_t * a)[..., None, None]
                 + (dt_t[..., None] * b_t[:, :, None, :])[..., :, None]
                 * x_t[..., None, :])
        return state, jnp.sum(state * c_t[:, :, None, :, None], axis=-2)

    @jax.checkpoint
    def stretch(state, of):
        return lax.scan(token, state, of)

    def by_stretch(v):
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape(s // block, block, *v.shape[1:])

    _, y = lax.scan(stretch,
                    jnp.zeros((b, g, r, b_in.shape[-1], p), jnp.float32),
                    tuple(by_stretch(v) for v in (x, dt, b_in, c_in)))
    return jnp.moveaxis(y.reshape(s, b, g, r, p), 0, 1)


def mamba(u, weights: dict, cfg: dict, precision: str):
    """u [B, S, h] -> [B, S, h]; `weights` under the module's own paths. No
    token sees another sequence's, so the sequences go one after the other,
    each recomputed in the backward pass: half of this mixer's float32
    arrays at two."""
    one = jax.checkpoint(lambda u_1: _mamba(
        u_1[None], weights, cfg, precision)[0])
    return lax.map(one, u)


def _mamba(u, weights: dict, cfg: dict, precision: str):
    b, s, _ = u.shape
    heads, size, state, groups = _widths(cfg)
    inner, mixed = heads * size, heads * size + 2 * groups * state
    zxbcdt = _product("bsh,hn->bsn", u, weights["in_proj/kernel"], precision)
    z = zxbcdt[..., :inner]
    xbc = jax.nn.silu(causal_taps(zxbcdt[..., inner:inner + mixed],
                                  weights["conv_taps"],
                                  weights["conv_bias"]))
    dt = jax.nn.softplus(zxbcdt[..., inner + mixed:] + weights["dt_bias"])
    by_group = (b, s, groups, heads // groups)
    x = xbc[..., :inner].reshape(*by_group, size)
    b_in, c_in = (v.reshape(b, s, groups, state)
                  for v in (xbc[..., inner:inner + groups * state],
                            xbc[..., inner + groups * state:]))
    y = ssm_scan(x, dt.reshape(by_group),
                 -jnp.exp(weights["A_log"]).reshape(by_group[2:]), b_in, c_in)
    y = y + weights["D"].reshape(by_group[2:])[..., None] * x
    v = (y.reshape(b, s, inner) * jax.nn.silu(z)).reshape(b, s, groups, -1)
    v = v * lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True)
                      + cfg["layer_norm_epsilon"])
    return _product("bsn,nh->bsh",
                    v.reshape(b, s, inner) * weights["norm_scale"],
                    weights["out_proj/kernel"], precision)


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


def plain_attention(u, weights: dict, cfg: dict, precision: str):
    """u [B, S, h] -> [B, S, h]; q and k are not turned: the family has no
    position embeddings."""
    q, k, v = (_product("bsh,hnd->bsnd", u, weights[name + "_proj/kernel"],
                        precision) for name in ("q", "k", "v"))
    return _product("bsnd,ndh->bsh", attention(q, k, v, precision),
                    weights["o_proj/kernel"], precision)


def relu2(x, w1, w2, precision: str):
    up = _product("th,hw->tw", x, w1, precision)
    return _product("tw,wh->th", jnp.square(jax.nn.relu(up)), w2, precision)


def gates(x, router, bias, cfg: dict):
    """(each token's chosen experts [T, top], their gates [T, top]) over
    ALL the router's outputs."""
    assert cfg["n_group"] == 1 and cfg["topk_group"] == 1
    scores = jax.nn.sigmoid(jnp.dot(x, router, precision=C.HIGHEST))
    _, chosen = lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return chosen, picked * cfg["routed_scaling_factor"]


def experts(x, weights: dict, cfg: dict, precision: str):
    """x [T, h] -> the held experts' part of the block's output, and the
    shared expert's."""
    held = cfg["n_routed_experts"]
    first = cfg["share"]["expert_share"] * held
    chosen, weight = gates(x, weights["router"], weights["router_bias"], cfg)

    @jax.checkpoint
    def add_expert(y, packed):
        e, w1, w2 = packed
        g_e = jnp.sum(jnp.where(chosen == first + e, weight, 0.0), axis=-1)
        return y + g_e[:, None] * relu2(x, w1, w2, precision), None

    # one expert after the other, as a loop of the program and not of its
    # text, each recomputed in the backward pass
    y, _ = lax.scan(add_expert, jnp.zeros_like(x),
                    (jnp.arange(held), weights["w1"], weights["w2"]))
    return y + relu2(x, weights["shared/w1"], weights["shared/w2"], precision)


def _under(params: dict, prefix: str) -> dict:
    return {p[len(prefix):]: v for p, v in params.items()
            if p.startswith(prefix)}


def block(x, weights: dict, cfg: dict, precision: str, kind: str):
    """x [B, S, h] through one block; `weights` under the block's own
    paths."""
    u = rms_norm(x, weights["norm/scale"], cfg["layer_norm_epsilon"])
    if kind == MAMBA:
        return x + mamba(u, _under(weights, "mixer/"), cfg, precision)
    if kind == ATTENTION:
        return x + plain_attention(u, _under(weights, "attn/"), cfg,
                                   precision)
    flat = u.reshape(-1, u.shape[-1])
    return x + experts(flat, _under(weights, "moe/"), cfg,
                       precision).reshape(x.shape)


def head_loss(x, head, targets, precision: str):
    """Mean cross-entropy of x [T, h] through `head` [h, V] against
    `targets` [T], `_TOKEN_BLOCK` tokens' logits at a time (filled up to
    whole blocks with tokens that count for nothing)."""
    tokens = x.shape[0]
    size = min(_TOKEN_BLOCK, tokens)
    fill = -tokens % size
    x = jnp.pad(x, ((0, fill), (0, 0))).reshape(-1, size, x.shape[-1])
    targets = jnp.pad(targets, (0, fill)).reshape(-1, size)
    counts = (jnp.arange(tokens + fill) < tokens).reshape(-1, size)

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
    # each block is recomputed in the backward pass
    one = jax.checkpoint(
        lambda x, w, kind: block(x, w, cfg, precision, kind),
        static_argnums=2)
    x = params["embed/embedding"][tokens]
    for i, kind in enumerate(blocks(cfg)):
        x = one(x, _under(params, f"blocks_{i}/"), kind)
    x = rms_norm(x, params["norm/scale"], cfg["layer_norm_epsilon"])
    return head_loss(x.reshape(-1, x.shape[-1]), params["lm_head"],
                     targets.reshape(-1), precision)
