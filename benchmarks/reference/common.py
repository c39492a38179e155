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


def unflatten(flat, like: dict) -> dict:
    """`like` maps each path to an array or to its shape."""
    out, off = {}, 0
    for k in sorted(like):
        shape = tuple(getattr(like[k], "shape", like[k]))
        n = 1
        for d in shape:
            n *= int(d)
        out[k] = flat[off:off + n].reshape(shape)
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


class _GradCalls:
    """The reference's one device program, `(tree, batch) -> (loss,
    gradient tree)`, compiled ahead of time for each batch shape so that
    XLA's own account of it can be handed to `probe`. `start` dispatches a
    call and returns at once; `wait` blocks until its result is there."""

    def __init__(self, loss_fn, probe, clock):
        self._fn = jax.jit(jax.value_and_grad(loss_fn))
        self._compiled = {}
        self._probe = probe or (lambda *a: None)
        self._clock = clock

    def start(self, tree, batch):
        args = (tree, batch)
        key = (jax.tree_util.tree_structure(args),
               tuple((a.shape, str(a.dtype))
                     for a in jax.tree_util.tree_leaves(args)))
        if key not in self._compiled:
            with self._clock("reference: gradient program compiled"):
                self._compiled[key] = self._fn.lower(*args).compile()
            self._probe("grad_compiled",
                        self._compiled[key].memory_analysis())
        with self._clock("reference: gradient calls"):
            self._probe("grad_call")
            return self._compiled[key](*args)

    def wait(self, out):
        with self._clock("reference: gradient calls"):
            out = jax.block_until_ready(out)
            self._probe("grad_returned")
        return out


# Elements of the flat vectors that one thread of the host's arithmetic takes
# at a time: elementwise float32, so the blocks change no bit; a block's
# operands and temporaries stay in a core's cache from one operation to the
# next, and no temporary is longer than this.
_CHUNK = 1 << 20
# Threads the blocks are shared among (numpy releases the interpreter's lock
# inside an operation).
_THREADS = 8


def _chunks(n: int):
    return (slice(lo, min(n, lo + _CHUNK)) for lo in range(0, n, _CHUNK))


# Elements of a device array that cross to the host in one copy, where the
# array is longer than two of them.
_SLICE = 1 << 25


def fetch(x, out=None):
    """The device array `x` on the host, as `np.asarray(x)` gives it (into
    `out` where one is given), made quick for an array of gigabytes: it
    crosses in slices of `_SLICE` elements, `_THREADS` copies in flight at
    a time. On the chip's host one whole copy of 2.4 GB takes 1 to 7 s (one
    thread of the runtime touches every fresh page of the destination,
    and a fresh page is dear there), the same array in 128 MB slices on
    threads 1 s. Each distinct shard is copied once, from the device that
    holds it; the slices are of the shard flattened there."""
    import numpy as np
    from concurrent.futures import ThreadPoolExecutor
    if out is None:
        out = np.empty(x.shape, x.dtype)
    if x.size < 2 * _SLICE:
        out[...] = np.asarray(x)
        return out
    jobs, seen = [], set()
    for shard in x.addressable_shards:
        index = tuple((s.start, s.stop) for s in shard.index)
        if index in seen:
            continue
        seen.add(index)
        to = out[shard.index]
        if not to.flags.c_contiguous:
            raise ValueError(f"shard {shard.index} of an array of shape "
                             f"{x.shape} is not one stretch of the host's")
        flat, to = shard.data.reshape(-1), to.reshape(-1)
        jobs += [(flat, to, lo) for lo in range(0, flat.size, _SLICE)]

    def copy(job):
        flat, to, lo = job
        to[lo:lo + _SLICE] = np.asarray(flat[lo:lo + _SLICE])

    with ThreadPoolExecutor(max_workers=_THREADS) as pool:
        for _ in pool.map(copy, jobs):
            pass
    return out


def _to_host(tree: dict, clock, out=None):
    """A {path: device array} dict as one float32 numpy vector in
    sorted-path order (`out` where one is given); each leaf is dropped
    from `tree`, and so from the device, as soon as it is here."""
    import numpy as np
    with clock("reference: gradients to the host"):
        if out is None:
            out = np.empty((sum(int(v.size) for v in tree.values()),),
                           np.float32)
        off = 0
        for k in sorted(tree):
            leaf = tree.pop(k)
            n = int(leaf.size)
            fetch(leaf, out[off:off + n].reshape(leaf.shape))
            off += n
    return out


def follow_steps(loss_fn, params: dict, shards, masks, *, lrs, momentum,
                 weight_decay, probe=None, clock=None):
    """Follow `len(shards)` optimizer steps from `params`.

    shards[s][w] is worker w's batch at step s (whatever `loss_fn(params,
    batch)` takes); masks[s][w] is a bool vector (True = worker w sent this
    entry at step s) or None for the dense baseline (everything is sent).
    Returns a dict of what the comparison reads: each step's loss (mean over
    workers), the first step's gradient (the workers' mean, and each
    worker's own), and the parameters after the last step, all flat float32.

    In bounded device memory, whatever the worker count. The optimizer's
    vectors, the residuals, the masks and the sums live on the HOST as
    float32 numpy, where the step's arithmetic is done in the same float32
    operations and the same order as the algorithm above is written
    (`acc = residual + g`, `sent = where(mask, acc, 0)`,
    `m = mu m + G + wd p`, `p = p - lr m`; IEEE add, multiply and divide
    round the same in numpy as in XLA), `_CHUNK` elements at a time on
    `_THREADS` threads (elementwise, so neither the blocks nor the threads
    change a bit), so that the host holds p, m, G, one residual a worker,
    the gradient in hand and the first step's gradients, and nothing else
    of full length. The device holds the parameter tree that the gradient
    is taken with respect to (4 bytes a parameter), one gradient (4 more),
    a worker's batch and what the gradient call needs while it runs. A
    gradient leaves the device as soon as it is there, and the next
    worker's call runs on the device while the host does this worker's
    arithmetic. Batches, masks and `params` may be host arrays.

    `probe(event, info=None)` is called with "grad_compiled" (info: XLA's
    `memory_analysis()` of the gradient program), "grad_call" before and
    "grad_returned" after each gradient call, and "step_end": for the
    harness's account of the check's memory and for the tests.
    `clock(name)` is a context manager that times a stretch of this
    thread's wall clock under `name` (`harness.Parts`): the run's
    `check by part` line.
    """
    import contextlib
    from concurrent.futures import ThreadPoolExecutor
    import numpy as np
    f32 = np.float32
    clock = clock or (lambda name: contextlib.nullcontext())
    calls = _GradCalls(loss_fn, probe, clock)
    like = {k: tuple(params[k].shape) for k in params}
    nworkers = len(shards[0])
    n = sum(int(np.prod(shape)) for shape in like.values())
    # a fresh page is dear on the chip's host and dearest to one thread: the
    # full-length vectors are made once, first touched by the threads, and
    # used again from step to step
    p, m, G = (np.empty((n,), f32) for _ in range(3))
    residual = [np.empty((n,), f32) for _ in range(nworkers)
                if masks[0] is not None]
    spare = None                # the gradient's buffer after the first step
    losses, first_grad, first_grads = [], None, []
    mu, wd, workers = f32(momentum), f32(weight_decay), f32(nworkers)

    with ThreadPoolExecutor(max_workers=_THREADS) as pool:

        def on_chunks(fn):
            with clock("reference: step arithmetic on the host"):
                for _ in pool.map(fn, _chunks(n)):
                    pass

        def laid_out(c):
            # p from `params`, leaf by leaf in sorted-path order; m and the
            # residuals 0
            lo = 0
            for k in sorted(like):
                leaf = np.asarray(params[k], f32).reshape(-1)
                a, b = max(c.start, lo), min(c.stop, lo + leaf.size)
                if a < b:
                    p[a:b] = leaf[a - lo:b - lo]
                lo += leaf.size
            m[c] = 0
            for r in residual:
                r[c] = 0

        on_chunks(laid_out)
        for s, step_shards in enumerate(shards):
            with clock("reference: parameters to the device"):
                tree = jax.block_until_ready(
                    jax.device_put(unflatten(p, like)))
            # the workers' mean gradient is read of the first step only;
            # of one worker it is that worker's gradient, and no vector of
            # its own (g + 0 and g / 1 are g)
            gsum = np.empty((n,), f32) if s == 0 and nworkers > 1 else None

            def cleared(c):
                G[c] = 0
                if gsum is not None:
                    gsum[c] = 0

            on_chunks(cleared)
            lr, loss = f32(lrs[s]), 0.0
            ahead = calls.start(tree, step_shards[0])
            for w in range(nworkers):
                l, g = calls.wait(ahead)
                loss += float(l) / nworkers
                g = _to_host(g, clock, spare)
                ahead = (calls.start(tree, step_shards[w + 1])
                         if w + 1 < nworkers else None)
                if s == 0:
                    first_grads.append(g)
                else:
                    spare = g
                mask = None if masks[s] is None else np.asarray(masks[s][w])

                def of_worker(c):
                    if gsum is not None:
                        np.add(gsum[c], g[c], out=gsum[c])
                    if mask is None:
                        np.add(G[c], g[c], out=G[c])
                    else:
                        acc = residual[w][c] + g[c]
                        sent = np.where(mask[c], acc, f32(0.0))
                        np.subtract(acc, sent, out=residual[w][c])
                        np.add(G[c], sent, out=G[c])

                on_chunks(of_worker)
                del g
            with clock("reference: vectors freed"):
                del tree

            def of_step(c):
                np.divide(G[c], workers, out=G[c])
                if gsum is not None:
                    np.divide(gsum[c], workers, out=gsum[c])
                m[c] = mu * m[c] + G[c] + wd * p[c]
                p[c] = p[c] - lr * m[c]

            on_chunks(of_step)
            if s == 0:
                first_grad = gsum if gsum is not None else first_grads[0]
            if probe is not None:
                probe("step_end")
            losses.append(loss)
        with clock("reference: vectors freed"):
            del m, residual, G, spare
    return {"losses": losses, "first_grad": first_grad, "params": p,
            "first_grad_workers": first_grads}
