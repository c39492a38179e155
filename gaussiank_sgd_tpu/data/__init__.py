"""Data pipelines (reference parity: dataset/dataloader construction in
``dl_trainer.py``, SURVEY.md §2 C5; plus AN4/WMT stand-ins for C9 and
BASELINE config 5).

``make_dataset(dataset, dnn, ...)`` dispatches by the reference's
``--dataset`` names: cifar10, cifar100, mnist, imagenet, ptb, an4, wmt14.
Real files are used when ``data_dir`` holds them; otherwise learnable
synthetic stand-ins (synthetic.py) keep everything runnable offline.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from .cifar import make_cifar, make_mnist
from .loader import (ArrayDataset, BucketedDataset, EpochStream,
                     batch_buffers, prefetch)
from .ptb import PTBDataset, make_ptb
from .synthetic import (flip_labels, synthetic_images, synthetic_images_u8,
                        synthetic_seq2seq, synthetic_spectrograms,
                        synthetic_tokens)


def make_imagenet(data_dir: Optional[str] = None, train: bool = True,
                  batch_size: int = 256, image_size: int = 224, seed: int = 0,
                  synthetic_examples: int = 1024) -> Tuple[ArrayDataset, int]:
    """ImageNet: synthetic stand-in unless a preprocessed .npy pair exists.

    Real-data path: ``{data_dir}/{split}_images.npy`` +
    ``{split}_labels.npy`` (preprocessing to packed arrays is done offline;
    full TFDS/grain integration is deliberately out of scope for this
    offline machine — SURVEY.md §7 hard part 5).

    Pixel dtype contract: batches are served as **uint8** whenever possible
    (synthetic path, or a u8 ``.npy``) and normalized ON DEVICE inside the
    jitted step (training/losses.py ``_prep_pixels``) — 4x less
    host->device traffic than pre-normalized f32, which is what lets the
    224^2 pipeline keep a chip fed. An f32
    ``.npy`` (already normalized offline) passes through unchanged.
    """
    split = "train" if train else "val"
    if data_dir and data_dir != "synthetic":
        import os
        xi = os.path.join(data_dir, f"{split}_images.npy")
        yi = os.path.join(data_dir, f"{split}_labels.npy")
        if os.path.exists(xi) and os.path.exists(yi):
            x = np.load(xi, mmap_mode="r")
            y = np.load(yi).astype(np.int32)
            return ArrayDataset((x, y), batch_size, shuffle=train,
                                seed=seed), 1000
    x, y = synthetic_images_u8(synthetic_examples,
                               (image_size, image_size, 3), 1000,
                               seed=0 if train else 1)
    return ArrayDataset((x, y), batch_size, shuffle=train, seed=seed), 1000


def make_an4(data_dir: Optional[str] = None, train: bool = True,
             batch_size: int = 16, seed: int = 0,
             synthetic_examples: int = 256, tgt_len: Optional[int] = None,
             widths: Tuple[int, ...] = (100, 200, 400, 800),
             freq: int = 161, time: int = 200,
             num_labels: Optional[int] = None):
    """AN4 speech (SURVEY.md §2 C9).

    Real-data path: ``{data_dir}/an4_{train|val}_manifest.csv`` in the
    DeepSpeech manifest format (``wav_path,transcript_path`` rows) —
    wav files featurize to log-spectrograms and batches form per quantized
    frame width (data/audio.py). Falls back to synthetic spectrogram/label
    pairs offline.

    ``tgt_len`` (label slots) is honored on BOTH paths when given; the
    default differs per path (64 for real transcripts, 8 for the short
    synthetic label strings) because real AN4 utterances are longer.
    """
    if data_dir and data_dir != "synthetic":
        import glob
        import os

        from .audio import NUM_LABELS, featurize_manifest
        split = "train" if train else "val"
        manifest = os.path.join(data_dir, f"an4_{split}_manifest.csv")
        if os.path.exists(manifest):
            buckets = featurize_manifest(manifest, widths,
                                         tgt_len=tgt_len or 64)
            return (_bucketed_from_arrays(buckets, batch_size, train, seed),
                    NUM_LABELS)
        other = glob.glob(os.path.join(data_dir, "an4_*_manifest.csv"))
        if other:
            # one split present but not the requested one: silently mixing
            # real audio with unrelated synthetic spectrograms would make
            # eval numbers meaningless — fail loudly instead
            raise FileNotFoundError(
                f"{manifest} not found, but {sorted(other)} exist in "
                f"{data_dir}; provide the {split} manifest (or use "
                f"data_dir='synthetic' for the all-synthetic fallback)")
    # ``freq``/``time``/``num_labels`` shrink the synthetic task for
    # toy-size CPU parity arms (the conv+biLSTM cost is ~linear in ``time``;
    # a smaller alphabet spreads the per-label frequency bands wider, so
    # CTC escapes its blank-dominated phase within a CPU-budget arm —
    # VERDICT r4 item 6); the real path ignores them — real wavs and the
    # AN4 charset dictate their own shapes
    nl = num_labels or 29
    x, y = synthetic_spectrograms(synthetic_examples, freq, time, nl,
                                  tgt_len or 8, seed=0 if train else 1)
    return ArrayDataset((x, y), batch_size, shuffle=train, seed=seed), nl


def _bucketed_from_arrays(buckets, batch_size: int, train: bool, seed: int):
    """Build a BucketedDataset, folding under-filled width buckets together
    (a pool must hold >= batch_size examples to yield a batch)."""
    def pad_to(x, w):
        return (np.pad(x, ((0, 0), (0, 0), (0, w - x.shape[2])))
                if x.shape[2] < w else x)

    merged, pending = [], None
    for x, y in buckets:                       # ascending widths
        if pending is not None:
            px, py = pending
            x = np.concatenate([pad_to(px, x.shape[2]), x])
            y = np.concatenate([py, y])
            pending = None
        if len(x) < batch_size:
            pending = (x, y)
        else:
            merged.append((x, y))
    if pending is not None:
        if merged:                             # fold widest leftover down
            x, y = merged[-1]
            px, py = pending
            w = max(x.shape[2], px.shape[2])
            merged[-1] = (np.concatenate([pad_to(x, w), pad_to(px, w)]),
                          np.concatenate([y, py]))
        else:
            raise ValueError(
                f"AN4 manifest has {len(pending[0])} usable examples, "
                f"fewer than batch_size={batch_size}")
    pools = [ArrayDataset((x, y), batch_size, shuffle=train, seed=seed + i)
             for i, (x, y) in enumerate(merged)]
    return BucketedDataset(pools, seed=seed)


def make_wmt(data_dir: Optional[str] = None, train: bool = True,
             batch_size: int = 64, src_len: int = 64, tgt_len: int = 64,
             vocab_size: int = 32000, seed: int = 0,
             synthetic_examples: int = 4096) -> Tuple[ArrayDataset, int]:
    """WMT14 En-De seq2seq batches (BASELINE config 5).

    Real-data path (same contract as PTB/AN4): ``{data_dir}/{split}.en`` +
    ``{split}.de`` parallel text, joint BPE vocab trained on the train split
    (data/wmt.py). A partially-present dataset (some ``*.en/*.de`` exist but
    not the requested split) fails loudly — silently mixing real and
    synthetic text would make eval numbers meaningless. Fully absent ->
    synthetic copy-reverse stand-in.
    """
    if data_dir and data_dir != "synthetic":
        import glob
        import os

        from .wmt import load_wmt_corpus
        split = "train" if train else "val"
        en = os.path.join(data_dir, f"{split}.en")
        de = os.path.join(data_dir, f"{split}.de")
        if os.path.exists(en) and os.path.exists(de):
            src, tgt, tok = load_wmt_corpus(data_dir, split, src_len,
                                            tgt_len, vocab_size)
            return (ArrayDataset((src, tgt), batch_size, shuffle=train,
                                 seed=seed), tok.vocab_size)
        other = [p for pat in ("*.en", "*.de")
                 for p in glob.glob(os.path.join(data_dir, pat))]
        if other:
            raise FileNotFoundError(
                f"{en} / {de} not found, but {sorted(other)} exist in "
                f"{data_dir}; provide the {split} split (or use "
                f"data_dir='synthetic' for the all-synthetic fallback)")
    src, tgt = synthetic_seq2seq(synthetic_examples, src_len, tgt_len,
                                 vocab_size, seed=0 if train else 1)
    return ArrayDataset((src, tgt), batch_size, shuffle=train, seed=seed), \
        vocab_size


def make_dataset(dataset: str, data_dir: Optional[str] = None,
                 train: bool = True, batch_size: int = 128, **kw):
    """Dispatch by --dataset name (SURVEY.md §2 C6 CLI). Returns
    (dataset, cardinality) where cardinality is num_classes / vocab /
    num_labels depending on the task."""
    dataset = dataset.lower()
    if dataset in ("cifar10", "cifar100"):
        return make_cifar(dataset, data_dir, train, batch_size, **kw)
    if dataset == "mnist":
        return make_mnist(data_dir, train, batch_size, **kw)
    if dataset == "imagenet":
        return make_imagenet(data_dir, train, batch_size, **kw)
    if dataset == "ptb":
        return make_ptb(data_dir, "train" if train else "valid", batch_size,
                        **kw)
    if dataset == "an4":
        return make_an4(data_dir, train, batch_size, **kw)
    if dataset in ("wmt14", "wmt"):
        return make_wmt(data_dir, train, batch_size, **kw)
    raise ValueError(f"unknown dataset {dataset!r}")
