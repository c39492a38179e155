"""Model zoo (reference parity: ``models/`` + torchvision imports, SURVEY.md
§2 C7/C8; extended with the Transformer target of BASELINE config 5).

``get_model(dnn, dataset)`` mirrors the reference CLI's ``--dnn`` dispatch in
``dl_trainer.py`` (SURVEY.md §2 C5 "model-zoo dispatch"): the same names the
reference accepts (``resnet20 ... resnet110, vgg16, alexnet, mnistnet,
resnet50, lstm, lstman4``) resolve here, plus ``transformer``.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax.numpy as jnp

from .afmoe import Afmoe
from .alexnet import AlexNet
from .joyai_flash import JoyAIFlash
from .lfm2_moe import LFM2MoE
from .lstm import LSTMLM
from .mellum2 import Mellum2
from .mnistnet import MnistNet
from .nemotron_h import NemotronH
from .qwen3_next import Qwen3Next
from .resnet import CifarResNet, ResNet50
from .speech import LSTMAN4
from .transformer import Transformer
from .transformer_lm import TransformerLM
from .vgg import VGG16


class ModelSpec(NamedTuple):
    name: str
    module: Any                       # flax linen module
    input_shape: Tuple[int, ...]      # single-example shape (no batch dim)
    input_dtype: Any
    num_classes: int
    task: str                         # 'classify' | 'lm' | 'ctc' | 'seq2seq'
    # the module's `__call__` takes `return_counters=True` and then answers
    # (output, {name: scalar}); the loss function logs them (`StepMetrics.aux`)
    counters: bool = False
    # > 0 (lm): the module takes `next_tokens=` (the targets) and then
    # answers (logits, logits two tokens ahead); the loss adds this much of
    # the second cross-entropy (a multi-token-prediction module)
    mtp_lambda: float = 0.0


_CIFAR = (32, 32, 3)
_IMAGENET = (224, 224, 3)
_MNIST = (28, 28, 1)


# images: name -> (class, one example's shape, the classes where none are
# given); `resnet<depth>` is built from its name
_IMAGE_MODELS = {
    "resnet50": (ResNet50, _IMAGENET, 1000),
    "vgg16": (VGG16, _CIFAR, 10),
    "alexnet": (AlexNet, _CIFAR, 10),
    "mnistnet": (MnistNet, _MNIST, 10),
}
_CIFAR_DEPTHS = (20, 32, 44, 56, 110)       # the reference's `resnet<depth>`


# the language models built from `blocks/`: name -> (class, the published
# vocabulary's rows, the weight of its multi-token-prediction module's loss
# or, where the model has no such module and takes no `mtp_lambda`, None).
# `vocab_size` is the rows held, `seq_len` only sizes the init input
_BLOCK_MODELS = {
    # sparse experts, window and full attention mixed (models/mellum2.py)
    "mellum2": (Mellum2, 98304, None),
    # latent attention, a sigmoid router with a shared expert, a leading
    # dense layer, a multi-token-prediction module (models/joyai_flash.py).
    # DeepSeek-V3 (arXiv:2412.19437, whose keys the config carries)
    # weighs the module's loss 0.3; the config itself gives no weight
    "joyai_flash": (JoyAIFlash, 129280, 0.3),
    # gated short convolutions and grouped-query attention with normed
    # heads three to one, a sigmoid router, the embedding for a head
    # (models/lfm2_moe.py)
    "lfm2_moe": (LFM2MoE, 65536, None),
    # window attention under rotary positions and full attention under
    # none three to one, a sigmoid gate on the attention's output, four
    # norms a layer, a sigmoid router with a shared expert, leading
    # dense layers (models/afmoe.py)
    "afmoe": (Afmoe, 200192, None),
    # a gated delta rule (linear attention with a carried state) and gated
    # attention at heads of 256 three to one, zero-centred norms, a softmax
    # router over 512 with a gated shared expert (models/qwen3_next.py)
    "qwen3_next": (Qwen3Next, 151936, None),
    # blocks of ONE module under one norm by a pattern string: Mamba-2
    # state-space mixers, squared-ReLU experts behind a sigmoid router with
    # a shared expert, attention under no positions (models/nemotron_h.py)
    "nemotron_h": (NemotronH, 131072, None),
}
_ALIASES = {"mnist": "mnistnet", "transformerlm": "transformer_lm"}


def _block_model(name: str, dtype, kw) -> ModelSpec:
    cls, vocab, mtp_lambda = _BLOCK_MODELS[name]
    vocab = kw.pop("vocab_size", vocab)
    seq_len = kw.pop("seq_len", 128)
    if mtp_lambda is not None:
        mtp_lambda = kw.pop("mtp_lambda", mtp_lambda)
    if kw.get("layer_types") is not None:
        kw["layer_types"] = tuple(kw["layer_types"])
    m = cls(vocab_size=vocab, dtype=dtype, **kw)
    if mtp_lambda is None or not m.num_nextn_predict_layers:
        mtp_lambda = 0.0
    return ModelSpec(name, m, (seq_len,), jnp.int32, vocab, "lm",
                     counters=True, mtp_lambda=mtp_lambda)


def get_model(dnn: str, dataset: Optional[str] = None, *,
              num_classes: Optional[int] = None,
              dtype=jnp.float32, **kw) -> ModelSpec:
    dnn = _ALIASES.get(dnn.lower(), dnn.lower())
    # **kw forwards to every module ctor (e.g. width/dropout overrides via
    # TrainConfig.model_kwargs) — never silently dropped
    image = _IMAGE_MODELS.get(dnn)
    if dnn.startswith("resnet") and dnn != "resnet50":
        kw.setdefault("depth", int(dnn[len("resnet"):]))
        image = (CifarResNet, _CIFAR, 100 if dataset == "cifar100" else 10)
    if image:
        cls, shape, nc = image
        nc = num_classes or nc
        return ModelSpec(dnn, cls(num_classes=nc, dtype=dtype, **kw), shape,
                         jnp.float32, nc, "classify")
    if dnn == "lstm":  # PTB language model (SURVEY.md §2 C8)
        vocab = kw.pop("vocab_size", 10000)
        m = LSTMLM(vocab_size=vocab, dtype=dtype, **kw)
        return ModelSpec("lstm", m, (35,), jnp.int32, vocab, "lm")
    if dnn == "lstman4":  # AN4 speech (SURVEY.md §2 C9)
        labels = kw.pop("num_labels", 29)
        m = LSTMAN4(num_labels=labels, dtype=dtype, **kw)
        return ModelSpec("lstman4", m, (161, 200), jnp.float32, labels, "ctc")
    if dnn == "transformer":  # BASELINE config 5 (new target, no ref model)
        vocab = kw.pop("vocab_size", 32000)
        seq_len = kw.pop("seq_len", 64)
        m = Transformer(vocab_size=vocab, dtype=dtype, **kw)
        return ModelSpec("transformer", m, (seq_len,), jnp.int32, vocab,
                         "seq2seq")
    if dnn == "transformer_lm":
        # decoder-only LM with optional ring-attention sequence parallelism
        # (long-context path; models/transformer_lm.py)
        vocab = kw.pop("vocab_size", 32000)
        seq_len = kw.pop("seq_len", 256)
        m = TransformerLM(vocab_size=vocab, dtype=dtype, **kw)
        return ModelSpec("transformer_lm", m, (seq_len,), jnp.int32, vocab,
                         "lm")
    if dnn in _BLOCK_MODELS:
        return _block_model(dnn, dtype, kw)
    raise ValueError(f"unknown dnn {dnn!r}; known: {', '.join(NAMES)}")


NAMES = (*(f"resnet{d}" for d in _CIFAR_DEPTHS), *_IMAGE_MODELS, "lstm",
         "lstman4", "transformer", "transformer_lm", *_BLOCK_MODELS)
# the names `get_model` takes (aliases included) whose head is a vocabulary:
# the trainer hands them the data set's cardinality as `vocab_size`
TOKEN_MODELS = frozenset(("lstm", "transformer", "transformer_lm",
                          "transformerlm", *_BLOCK_MODELS))
