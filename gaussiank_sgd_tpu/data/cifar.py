"""CIFAR-10/100 pipeline: binary-file reader with synthetic fallback.

Reference parity: the torchvision CIFAR pipeline in ``dl_trainer.py``
(SURVEY.md §2 C5) with the standard augmentation (pad-4 random crop +
horizontal flip) and per-channel normalization. Reads the canonical
``cifar-10-batches-bin`` / ``cifar-100-binary`` layouts if present under
``data_dir``; otherwise serves the learnable synthetic stand-in
(data/synthetic.py) so offline machines still train end-to-end.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import numpy as np

from .loader import ArrayDataset, fill_sliced
from .synthetic import flip_labels, synthetic_images

# standard CIFAR-10 channel stats
_MEAN = np.array([0.4914, 0.4822, 0.4465], np.float32)
_STD = np.array([0.2470, 0.2435, 0.2616], np.float32)


def _read_cifar10_bin(data_dir: str, train: bool):
    names = ([f"data_batch_{i}.bin" for i in range(1, 6)] if train
             else ["test_batch.bin"])
    sub = os.path.join(data_dir, "cifar-10-batches-bin")
    base = sub if os.path.isdir(sub) else data_dir
    xs, ys = [], []
    for n in names:
        raw = np.fromfile(os.path.join(base, n), np.uint8)
        rec = raw.reshape(-1, 3073)
        ys.append(rec[:, 0])
        xs.append(rec[:, 1:].reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1))
    return np.concatenate(xs), np.concatenate(ys).astype(np.int32)


def _read_cifar100_bin(data_dir: str, train: bool):
    name = "train.bin" if train else "test.bin"
    sub = os.path.join(data_dir, "cifar-100-binary")
    base = sub if os.path.isdir(sub) else data_dir
    raw = np.fromfile(os.path.join(base, name), np.uint8)
    rec = raw.reshape(-1, 3074)  # coarse label, fine label, 3072 pixels
    y = rec[:, 1].astype(np.int32)
    x = rec[:, 2:].reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)
    return x, y


def _normalize(x_u8: np.ndarray) -> np.ndarray:
    return ((x_u8.astype(np.float32) / 255.0) - _MEAN) / _STD


_PAD = 4


def _reflect(p: np.ndarray, n: int) -> np.ndarray:
    """Where position ``p`` of an axis of length ``n`` reads from once the
    axis is padded as ``np.pad(mode="reflect")`` pads it (mirrored about
    the edge element, the edge not repeated); ``-n < p < 2n - 1``."""
    p = np.abs(p)
    return np.where(p >= n, 2 * (n - 1) - p, p)


def _take_crops(pixels: np.ndarray, out: np.ndarray, sel: np.ndarray,
                rows: np.ndarray, cols: np.ndarray, lo: int, hi: int):
    """Fill ``out[lo:hi]``: image i's pixel (r, c) is pixel
    (rows[i, r], cols[i, c]) of image ``sel[i]``, read from the data set's
    ``(N*h*w, c)`` pixel view in one indexed copy."""
    _, h, w, c = out.shape
    src = ((sel[lo:hi, None] * h + rows[lo:hi])[:, :, None] * w
           + cols[lo:hi, None, :])
    # mode="clip": the indices are in range by construction, and "raise"
    # would fill a buffer and copy it into ``out`` afterwards
    np.take(pixels, src.reshape(-1), axis=0,
            out=out[lo:hi].reshape(-1, c), mode="clip")


def _augment(rng: np.random.Generator):
    """Pad-4 reflect random crop and horizontal flip as ``ArrayDataset``'s
    ``augment``. The three draws are made for the whole batch first; crop,
    border and flip then are index arithmetic on ``b x 32`` integers, and
    the pixels are copied once, from the data set's array into the batch,
    in slices (``loader.fill_sliced``; ``slices`` is for the tests)."""
    def fn(arrays, sel: np.ndarray, slices: Optional[int] = None):
        x, y = arrays
        b = len(sel)
        _, h, w, c = x.shape
        oy = rng.integers(0, 2 * _PAD + 1, size=b)
        ox = rng.integers(0, 2 * _PAD + 1, size=b)
        flip = rng.random(b) < 0.5
        rows = _reflect(oy[:, None] + np.arange(h) - _PAD, h)
        across = np.arange(w)
        cols = _reflect(ox[:, None] - _PAD
                        + np.where(flip[:, None], w - 1 - across, across), w)
        pixels = x.reshape(-1, c)       # a view: make_cifar keeps x contiguous
        out = np.empty((b, h, w, c), x.dtype)
        fill_sliced(lambda lo, hi: _take_crops(pixels, out, sel, rows, cols,
                                               lo, hi), b, slices)
        return out, y[sel]
    return fn


class CifarPipeline:
    """Batch pipeline over raw u8 CIFAR records using the native C++
    assembler (data/native.py; gather + normalize + pad-4 reflect crop +
    hflip in one threaded pass) — the rebuild's equivalent of the torch
    DataLoader worker pool (SURVEY.md §2.1). Interface-compatible with
    ArrayDataset (steps_per_epoch / epoch / __iter__)."""

    def __init__(self, x_u8: np.ndarray, y: np.ndarray, batch_size: int,
                 shuffle: bool = True, augment: bool = True, seed: int = 0):
        from . import native
        assert native.available()
        self._native = native
        self.x_u8 = np.ascontiguousarray(x_u8)
        self.y = y.astype(np.int32)
        self.batch_size = int(batch_size)
        self.shuffle = shuffle
        self.augment = augment
        self.seed = seed
        self.num_examples = len(x_u8)
        self.steps_per_epoch = self.num_examples // self.batch_size
        self._epoch = 0

    def epoch(self, epoch_seed: Optional[int] = None):
        e = self._epoch if epoch_seed is None else epoch_seed
        self._epoch += 1
        if self.shuffle:
            order = self._native.shuffle_indices(self.num_examples,
                                                 self.seed * 1_000_003 + e)
        else:
            order = np.arange(self.num_examples, dtype=np.int32)
        for s in range(self.steps_per_epoch):
            sel = order[s * self.batch_size:(s + 1) * self.batch_size]
            yield self._native.assemble_batch(
                self.x_u8, self.y, sel, _MEAN, _STD,
                seed=(self.seed * 7_919 + e) * 100_003 + s,
                augment=self.augment)

    def __iter__(self):
        while True:
            yield from self.epoch()


def make_cifar(dataset: str = "cifar10", data_dir: Optional[str] = None,
               train: bool = True, batch_size: int = 128,
               augment: bool = True, seed: int = 0,
               synthetic_examples: int = 2048,
               use_native: bool = True,
               label_noise: float = 0.0) -> Tuple[ArrayDataset, int]:
    """Returns (dataset, num_classes). ``label_noise``: symmetric label-flip
    fraction applied to BOTH splits (synthetic.flip_labels) — makes the
    top-1 ceiling 1-p so convergence-parity experiments can fail."""
    from . import native
    num_classes = 100 if dataset == "cifar100" else 10
    x = x_u8 = None
    if data_dir and data_dir != "synthetic":
        try:
            reader = (_read_cifar100_bin if dataset == "cifar100"
                      else _read_cifar10_bin)
            x_u8, y = reader(data_dir, train)
        except FileNotFoundError:
            x_u8 = None
    if x_u8 is not None:
        y = flip_labels(y, num_classes, label_noise, seed=0 if train else 1)
        if use_native and native.available():
            return CifarPipeline(x_u8, y, batch_size, shuffle=train,
                                 augment=train and augment,
                                 seed=seed), num_classes
        x = _normalize(x_u8)
    if x is None:
        x, y = synthetic_images(synthetic_examples, (32, 32, 3), num_classes,
                                seed=0 if train else 1)
        y = flip_labels(y, num_classes, label_noise, seed=0 if train else 1)
    aug = _augment(np.random.default_rng(seed)) if (train and augment) else None
    ds = ArrayDataset((np.ascontiguousarray(x), y), batch_size,
                      shuffle=train, seed=seed,
                      augment=aug)
    return ds, num_classes


def make_mnist(data_dir: Optional[str] = None, train: bool = True,
               batch_size: int = 128, seed: int = 0,
               synthetic_examples: int = 2048,
               label_noise: float = 0.0) -> Tuple[ArrayDataset, int]:
    """MNIST via idx files if present, else synthetic (SURVEY.md §2 C7).
    ``label_noise``: see make_cifar."""
    x = None
    if data_dir and data_dir != "synthetic":
        try:
            img = "train-images-idx3-ubyte" if train else "t10k-images-idx3-ubyte"
            lab = "train-labels-idx1-ubyte" if train else "t10k-labels-idx1-ubyte"
            with open(os.path.join(data_dir, img), "rb") as f:
                xi = np.frombuffer(f.read(), np.uint8, offset=16)
            with open(os.path.join(data_dir, lab), "rb") as f:
                y = np.frombuffer(f.read(), np.uint8, offset=8).astype(np.int32)
            x = (xi.reshape(-1, 28, 28, 1).astype(np.float32) / 255.0 - 0.1307) / 0.3081
        except FileNotFoundError:
            x = None
    if x is None:
        x, y = synthetic_images(synthetic_examples, (28, 28, 1), 10,
                                seed=0 if train else 1)
    y = flip_labels(y, 10, label_noise, seed=0 if train else 1)
    return ArrayDataset((x, y), batch_size, shuffle=train, seed=seed), 10
