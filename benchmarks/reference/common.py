"""The plain reference's shared arithmetic: float32 `jax.numpy`, matrix
products at `highest` precision, no kernel, no flat optimizer, no Trainer.

It imports nothing of `gaussiank_sgd_tpu` and takes nothing that the program
has made. Weights come from `init_params` of the configuration's own reference
file (from the run's seed), inputs are the rows that were fed to the program,
and the only thing read back from the program is WHICH entries each worker
sent at each step (the mask): the discrete answer of the system under test,
which the reference is teacher-forced with, the way a served model's reference
is run over the tokens that were served.

Published algorithm (Shi et al., arXiv:1911.08772, Alg. 1, with momentum SGD
as in the upstream's trainer):

    acc_w   = residual_w + g_w                 per worker w
    sent_w  = acc_w on the selected entries, 0 elsewhere
    residual_w' = acc_w - sent_w               (error feedback)
    G       = mean_w sent_w
    m'      = mu * m + G + wd * p              (torch SGD: decay before momentum)
    p'      = p - lr * m'

The dense baseline is the same with every entry selected.

`precision` is the control's knob: "float32" is the reference; "float8" puts
the same mathematics through 8-bit matrix products, operands in e4m3 forward
and cotangents in e5m2 backward, one scale per tensor, accumulated in float32
(the step below the configuration's bfloat16 that a later change could be
tempted by); "bfloat16" rounds the same places the way the configuration
states, for tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
_E4M3_MAX = 448.0


_E5M2_MAX = 57344.0


def _to_float8(x, dtype, top):
    """Round to an 8-bit float with one scale for the whole tensor (its
    largest magnitude lands on the format's largest value)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, top / amax, 1.0)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


def _round_forward(x, precision):
    """An operand of a matrix product as the precision holds it; the rounded
    value forward, the identity backward (straight-through)."""
    if precision == "float32":
        return x
    if precision == "bfloat16":
        q = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif precision == "float8":
        q = _to_float8(x, jnp.float8_e4m3fn, _E4M3_MAX)
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return x + lax.stop_gradient(q - x)


def _round_backward(y, precision):
    """The product's output: the identity forward; backward its cotangent,
    the operand of both backward products, is rounded as the precision holds
    gradients (e5m2 under float8, the usual pairing with e4m3 forward:
    Micikevicius et al., arXiv:2209.05433)."""
    if precision == "float32":
        return y

    @jax.custom_vjp
    def ident(v):
        return v

    def fwd(v):
        return v, None

    def bwd(_, g):
        if precision == "bfloat16":
            return (g.astype(jnp.bfloat16).astype(jnp.float32),)
        return (_to_float8(g, jnp.float8_e5m2, _E5M2_MAX),)

    ident.defvjp(fwd, bwd)
    return ident(y)


def conv(x, w, stride=1, pad=0, precision="float32"):
    """NHWC x HWIO convolution, explicit symmetric padding."""
    x, w = _round_forward(x, precision), _round_forward(w, precision)
    y = lax.conv_general_dilated(
        x, w, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
    return _round_backward(y, precision)


def dense(x, w, b, precision="float32"):
    x, w = _round_forward(x, precision), _round_forward(w, precision)
    return _round_backward(jnp.dot(x, w, precision=HIGHEST), precision) + b


def batch_norm(x, scale, bias, eps=1e-5):
    """Training-mode batch normalisation over (N, H, W): this batch's own
    mean and biased variance."""
    axes = tuple(range(x.ndim - 1))
    mean = jnp.mean(x, axis=axes)
    var = jnp.mean(jnp.square(x - mean), axis=axes)
    return (x - mean) * lax.rsqrt(var + eps) * scale + bias


def max_pool(x, window, stride, pad=0):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, window, window, 1), (1, stride, stride, 1),
        ((0, 0), (pad, pad), (pad, pad), (0, 0)))


def cross_entropy(logits, labels):
    """Mean softmax cross-entropy with integer labels."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - picked)


def normal_init(key, shape, std):
    return std * jax.random.normal(key, shape, jnp.float32)


# ------------------------------------------------------------ training steps

def flatten(tree: dict) -> jax.Array:
    """Leaves of a {path: array} dict in sorted-path order, as one vector.
    The harness lays the program's parameters out the same way."""
    return jnp.concatenate([tree[k].reshape(-1) for k in sorted(tree)])


def unflatten(flat: jax.Array, like: dict) -> dict:
    out, off = {}, 0
    for k in sorted(like):
        n = like[k].size
        out[k] = flat[off:off + n].reshape(like[k].shape)
        off += n
    return out


def lr_at(step: int, base_lr: float, nworkers: int, warmup_steps: int) -> float:
    """Goyal et al.'s gradual warm-up as the upstream uses it: base_lr on one
    worker; on P workers a linear ramp base_lr -> P * base_lr. (The
    milestones lie far beyond the steps the check follows.)"""
    if nworkers <= 1:
        return base_lr
    frac = min(max(step / max(1, warmup_steps), 0.0), 1.0)
    return base_lr + (base_lr * nworkers - base_lr) * frac


def follow_steps(loss_fn, params: dict, shards, masks, *, lrs, momentum,
                 weight_decay):
    """Follow `len(shards)` optimizer steps from `params`.

    shards[s][w] is worker w's batch at step s (whatever `loss_fn(params,
    batch)` takes); masks[s][w] is a bool vector (True = worker w sent this
    entry at step s) or None for the dense baseline (everything is sent).
    Returns a dict of what the comparison reads: each step's loss (mean over
    workers), the first step's gradient (the workers' mean, and each
    worker's own), and the parameters after the last step, all flat float32.
    """
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    p = flatten(params)
    m = jnp.zeros_like(p)
    nworkers = len(shards[0])
    residual = [jnp.zeros_like(p) for _ in range(nworkers)]
    losses, first_grad, first_grads = [], None, []
    for s, step_shards in enumerate(shards):
        tree = unflatten(p, params)
        G = jnp.zeros_like(p)
        gsum = jnp.zeros_like(p)
        loss = 0.0
        for w, batch in enumerate(step_shards):
            l, g = grad_fn(tree, batch)
            g = flatten(g)
            loss += float(l) / nworkers
            gsum = gsum + g
            if s == 0:
                first_grads.append(g)
            if masks[s] is None:
                G = G + g
            else:
                acc = residual[w] + g
                sent = jnp.where(masks[s][w], acc, 0.0)
                residual[w] = acc - sent
                G = G + sent
        G = G / nworkers
        if first_grad is None:
            first_grad = gsum / nworkers
        losses.append(loss)
        m = momentum * m + G + weight_decay * p
        p = p - lrs[s] * m
    return {"losses": losses, "first_grad": first_grad, "params": p,
            "first_grad_workers": first_grads}
