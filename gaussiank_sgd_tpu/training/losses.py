"""Per-task loss functions, shaped for the train step's LossFn contract
``(params, model_state, batch, rng) -> (loss, (model_state', aux))``.

Reference parity: the loss dispatch in ``DLTrainer`` (SURVEY.md §3.2 —
"CE / CTC(an4) / CE-per-token(ptb)"), plus label-smoothed seq2seq CE for the
Transformer target (BASELINE config 5).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax

from ..models import ModelSpec


# ImageNet channel stats for the device-side u8 path (torchvision's, the
# reference's own normalization constants)
IMAGENET_NORM = (jnp.asarray([0.485, 0.456, 0.406], jnp.float32),
                 jnp.asarray([0.229, 0.224, 0.225], jnp.float32))


def _prep_pixels(x, input_norm):
    """Normalize uint8 pixels ON DEVICE, inside the jitted step.

    TPU-first input-pipeline design (SURVEY.md §7 hard part 5): datasets
    ship uint8 — 4x less host->device traffic than pre-normalized f32 —
    and XLA fuses this cast+scale into the first convolution. Float inputs
    (pre-normalized offline, or synthetic) pass through untouched; the
    dtype check is trace-time static.
    """
    if input_norm is not None and x.dtype == jnp.uint8:
        mean, std = input_norm
        return (x.astype(jnp.float32) / 255.0 - mean) / std
    return x


def _apply(spec: ModelSpec, params, mstate, rng, *inputs, **extra):
    """Train-mode apply, threading mutable collections + dropout rng."""
    variables = {"params": params, **mstate}
    mutable = [k for k in mstate.keys()]
    kwargs = dict(train=True, rngs={"dropout": rng}, **extra)
    if mutable:
        out, updated = spec.module.apply(variables, *inputs,
                                         mutable=mutable, **kwargs)
        return out, updated
    return spec.module.apply(variables, *inputs, **kwargs), mstate


def make_loss_fn(spec: ModelSpec, label_smoothing: float = 0.0,
                 recurrent: bool = False,
                 input_norm: Optional[Callable] = None) -> Callable:
    """``recurrent=True`` (lm only): the carry-threading LossFn protocol of
    parallel/trainstep.py — consume the previous window's hidden state,
    return the new one (the reference's bptt repackaging, SURVEY.md §3.2).

    ``input_norm``: (mean, std) for uint8 pixel batches, applied on device
    (see _prep_pixels); ignored for float/token inputs."""
    task = spec.task

    if recurrent:
        assert task == "lm", f"carry threading is for lm models, not {task}"

        def loss_fn(params, mstate, batch, rng, carry):
            x, y = batch
            (logits, new_carry), mstate = _apply(
                spec, params, mstate, rng, x,
                initial_carry=carry, return_carry=True)
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
            return loss, (mstate, {"ce_per_token": loss}, new_carry)
        return loss_fn

    if task == "classify":
        def loss_fn(params, mstate, batch, rng):
            x, y = batch
            x = _prep_pixels(x, input_norm)
            logits, mstate = _apply(spec, params, mstate, rng, x)
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
            acc = (logits.argmax(-1) == y).astype(jnp.float32).mean()
            return loss, (mstate, {"acc": acc})
        return loss_fn

    if task == "lm":
        def loss_fn(params, mstate, batch, rng):
            x, y = batch
            # a model with counters of its own (`ModelSpec.counters`) hands
            # them out beside its logits; they ride the auxiliary output
            # into the `train` record
            counters, extra = {}, {}
            if spec.counters:
                extra["return_counters"] = True
            if spec.mtp_lambda:
                extra["next_tokens"] = y
            out, mstate = _apply(spec, params, mstate, rng, x, **extra)
            if spec.counters:
                out, counters = out
            logits, ahead = out if spec.mtp_lambda else (out, None)
            # device scope `loss`: the cross-entropy over the float32
            # logits and its backward pass (docs/OBSERVABILITY.md)
            with jax.named_scope("loss"):
                loss = ce = optax.softmax_cross_entropy_with_integer_labels(
                    logits, y).mean()
            if spec.mtp_lambda:
                # a multi-token-prediction module: at position t it saw
                # y_t's embedding and predicts y_{t+1}; the last position
                # has no such target
                with jax.named_scope("loss"):
                    ce_mtp = optax.softmax_cross_entropy_with_integer_labels(
                        ahead[:, :-1], y[:, 1:]).mean()
                loss = ce + spec.mtp_lambda * ce_mtp
                counters = {"ce_mtp_per_token": ce_mtp, **counters}
            # perplexity = exp(ce); report it, exp on host
            return loss, (mstate, {"ce_per_token": ce, **counters})
        return loss_fn

    if task == "ctc":
        def loss_fn(params, mstate, batch, rng):
            x, labels = batch
            logits, mstate = _apply(spec, params, mstate, rng, x)
            logit_pad = jnp.zeros(logits.shape[:2], jnp.float32)
            label_pad = (labels == 0).astype(jnp.float32)
            loss = optax.ctc_loss(logits, logit_pad, labels,
                                  label_pad).mean()
            return loss, (mstate, {"ctc": loss})
        return loss_fn

    if task == "seq2seq":
        def loss_fn(params, mstate, batch, rng):
            src, tgt = batch
            # teacher forcing: decoder input is tgt shifted right (BOS=pad 0)
            dec_in = jnp.pad(tgt[:, :-1], ((0, 0), (1, 0)))
            logits, mstate = _apply(spec, params, mstate, rng, src, dec_in)
            mask = (tgt != 0).astype(jnp.float32)
            if label_smoothing > 0:
                n = logits.shape[-1]
                onehot = jax.nn.one_hot(tgt, n)
                soft = onehot * (1 - label_smoothing) + label_smoothing / n
                ce = optax.softmax_cross_entropy(logits, soft)
            else:
                ce = optax.softmax_cross_entropy_with_integer_labels(
                    logits, tgt)
            loss = (ce * mask).sum() / jnp.maximum(mask.sum(), 1.0)
            acc = (((logits.argmax(-1) == tgt) * mask).sum()
                   / jnp.maximum(mask.sum(), 1.0))
            return loss, (mstate, {"acc": acc})
        return loss_fn

    raise ValueError(f"unknown task {task!r}")


def ctc_greedy_decode(logits: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Greedy (best-path) CTC decode: per-frame argmax, collapse repeats,
    drop blanks (blank_id = 0, optax.ctc_loss's default and the label-pad
    convention of data/audio.py).

    Reference parity: the reference's AN4 eval decodes with its decoder
    class over log-probs (SURVEY.md §2 C9); greedy best-path is the
    deterministic core of that. Returns ``(ids, mask)`` — the decoded
    string is ids[mask], kept un-compacted (static shapes) because the
    edit-distance DP below consumes masked sequences directly.
    """
    ids = logits.argmax(-1)                              # [B, T]
    prev = jnp.pad(ids[:, :-1], ((0, 0), (1, 0)), constant_values=-1)
    mask = (ids != 0) & (ids != prev)
    return ids, mask


def _edit_distance_one(hyp, hyp_mask, ref, ref_mask):
    """Levenshtein distance between masked sequences (jit-shaped DP).

    Row j holds d(hyp-consumed-so-far, ref[:j]); masked-out hyp frames
    leave the row untouched, so no compaction is needed. O(T*U) lax.scan
    steps — eval-only cost at AN4 shapes.
    """
    from jax import lax

    u = ref.shape[0]
    ref_len = jnp.sum(ref_mask.astype(jnp.int32))
    row0 = jnp.arange(u + 1, dtype=jnp.int32)

    def outer(row, inp):
        h, valid = inp

        def inner(diag_new, cell):
            row_j, row_jm1, ref_c = cell
            v = jnp.minimum(jnp.minimum(row_j + 1, diag_new + 1),
                            row_jm1 + jnp.where(h == ref_c, 0, 1))
            return v, v

        first = row[0] + 1
        _, rest = lax.scan(inner, first, (row[1:], row[:-1], ref))
        new_row = jnp.concatenate([first[None], rest])
        return jnp.where(valid, new_row, row), None

    row, _ = lax.scan(outer, row0, (hyp, hyp_mask))
    return row[ref_len], ref_len


def char_error_counts(logits: jax.Array, labels: jax.Array,
                      ) -> tuple[jax.Array, jax.Array]:
    """(edit_distance_sum, ref_char_sum) for a batch — CER numerator and
    denominator, summable across eval shards (labels == 0 is padding)."""
    hyp, hyp_mask = ctc_greedy_decode(logits)
    ref_mask = labels != 0
    edits, ref_lens = jax.vmap(_edit_distance_one)(hyp, hyp_mask,
                                                   labels, ref_mask)
    return (jnp.sum(edits).astype(jnp.float32),
            jnp.sum(ref_lens).astype(jnp.float32))


def make_eval_fn(spec: ModelSpec, recurrent: bool = False,
                 input_norm: Optional[Callable] = None) -> Callable:
    """(params, mstate, batch) -> dict of SUMS (caller psums + normalizes).

    Eval-mode apply (train=False, running BatchNorm stats, no dropout).
    Returns sums so distributed eval just adds across shards — top-1/top-5/
    val-loss/perplexity exactly as the reference's test loop (SURVEY.md §2 C5).

    ``recurrent=True`` (lm only): signature becomes
    ``(params, mstate, batch, carry) -> (sums, new_carry)`` so the eval loop
    threads hidden state across the contiguous bptt windows of the test
    stream — the reference evaluates perplexity with carried state too.
    """
    task = spec.task

    def apply_eval(params, mstate, *inputs, **extra):
        return spec.module.apply({"params": params, **mstate}, *inputs,
                                 train=False, **extra)

    if recurrent:
        assert task == "lm", f"carry threading is for lm models, not {task}"

        def eval_fn(params, mstate, batch, carry):
            x, y = batch
            logits, new_carry = apply_eval(params, mstate, x,
                                           initial_carry=carry,
                                           return_carry=True)
            ce = optax.softmax_cross_entropy_with_integer_labels(logits, y)
            return ({"loss_sum": ce.sum(),
                     "n": jnp.float32(y.shape[0] * y.shape[1])}, new_carry)
        return eval_fn

    if task == "classify":
        def eval_fn(params, mstate, batch):
            x, y = batch
            x = _prep_pixels(x, input_norm)
            logits = apply_eval(params, mstate, x)
            ce = optax.softmax_cross_entropy_with_integer_labels(logits, y)
            top1 = (logits.argmax(-1) == y).sum()
            top5 = (jax.lax.top_k(logits, min(5, logits.shape[-1]))[1]
                    == y[:, None]).any(-1).sum()
            return {"loss_sum": ce.sum(), "top1": top1.astype(jnp.float32),
                    "top5": top5.astype(jnp.float32),
                    "n": jnp.float32(y.shape[0])}
        return eval_fn

    if task == "lm":
        def eval_fn(params, mstate, batch):
            x, y = batch
            logits = apply_eval(params, mstate, x)
            ce = optax.softmax_cross_entropy_with_integer_labels(logits, y)
            return {"loss_sum": ce.sum(),
                    "n": jnp.float32(y.shape[0] * y.shape[1])}
        return eval_fn

    if task == "ctc":
        def eval_fn(params, mstate, batch):
            x, labels = batch
            logits = apply_eval(params, mstate, x)
            logit_pad = jnp.zeros(logits.shape[:2], jnp.float32)
            label_pad = (labels == 0).astype(jnp.float32)
            loss = optax.ctc_loss(logits, logit_pad, labels, label_pad)
            # task-level quality (VERDICT r3 item 5): greedy decode + CER
            # sums; the caller reports cer = edit_sum / ref_char_sum
            edit_sum, ref_sum = char_error_counts(logits, labels)
            return {"loss_sum": loss.sum(), "cer_edit_sum": edit_sum,
                    "cer_ref_sum": ref_sum,
                    "n": jnp.float32(labels.shape[0])}
        return eval_fn

    if task == "seq2seq":
        def eval_fn(params, mstate, batch):
            src, tgt = batch
            dec_in = jnp.pad(tgt[:, :-1], ((0, 0), (1, 0)))
            logits = apply_eval(params, mstate, src, dec_in)
            mask = (tgt != 0).astype(jnp.float32)
            ce = optax.softmax_cross_entropy_with_integer_labels(logits, tgt)
            top1 = ((logits.argmax(-1) == tgt) * mask).sum()
            return {"loss_sum": (ce * mask).sum(), "top1": top1,
                    "n": mask.sum()}
        return eval_fn

    raise ValueError(f"unknown task {task!r}")
