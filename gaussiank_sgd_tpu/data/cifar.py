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

from .loader import ArrayDataset, batch_buffers, fill_sliced
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

# Rows gathered by one indexed read (256 images' worth): the read makes an
# array of its own before it is copied into the batch, so it is kept to a
# few megabytes, and long enough that numpy lets go of the GIL for it and
# the slices' threads do not queue for the interpreter between reads.
_ROWS_AT_ONCE = 256 * 32


def _padded_both_ways(x: np.ndarray) -> np.ndarray:
    """``x`` (``[N, h, w, c]``) padded by ``_PAD`` as
    ``np.pad(mode="reflect")`` pads it, and behind it the same images
    mirrored left to right: ``[2N, h + 2*_PAD, w + 2*_PAD, c]``, contiguous.
    In it every row of every crop, flipped or not, is one run of ``w``
    pixels."""
    n = len(x)
    both = np.empty((2 * n, x.shape[1] + 2 * _PAD, x.shape[2] + 2 * _PAD,
                     x.shape[3]), x.dtype)

    def fill(lo: int, hi: int) -> None:
        both[lo:hi] = np.pad(
            x[lo:hi], ((0, 0), (_PAD, _PAD), (_PAD, _PAD), (0, 0)),
            mode="reflect")
        both[n + lo:n + hi] = both[lo:hi, :, ::-1]
    fill_sliced(fill, n)
    return both


def _take_rows(runs: np.ndarray, rows: np.ndarray, src: np.ndarray,
               lo: int, hi: int) -> None:
    """Fill ``rows[lo:hi]``: row k of the batch is run ``src[k]``."""
    for a in range(lo, hi, _ROWS_AT_ONCE):
        z = min(a + _ROWS_AT_ONCE, hi)
        rows[a:z] = runs[src[a:z]]


def _augment(rng: np.random.Generator, x: np.ndarray):
    """Pad-4 reflect random crop and horizontal flip over the images ``x``
    as ``ArrayDataset``'s ``augment`` (whose ``arrays`` are ``x`` and the
    labels). Border and flip are paid once, here: the images are kept
    padded and in both orientations (:func:`_padded_both_ways`, 6.25 times
    the bytes of ``x``), so a batch is the three draws, made for the whole
    batch first, ``b x 32`` integers of arithmetic (where each row of each
    crop starts), and one copy of each row, ``w`` pixels in one piece, from
    that array into a recycled buffer (``loader.batch_buffers``), in slices
    (``loader.fill_sliced``; ``slices`` is for the tests)."""
    n, h, w, c = x.shape
    hp, wp = h + 2 * _PAD, w + 2 * _PAD
    both = _padded_both_ways(x)
    # every run of w pixels in ``both``, run p the one that starts at pixel
    # p, as the elements of a 1-D array: numpy gathers those with one
    # memcpy a row, where a window of a 2-D view costs it an iterator a row
    run = np.dtype((np.void, w * c * x.itemsize))
    runs = np.ndarray((both.size // c - w + 1,), run, buffer=both,
                      strides=(c * x.itemsize,))
    down = np.arange(h)

    def fn(arrays, sel: np.ndarray, slices: Optional[int] = None):
        b = len(sel)
        oy = rng.integers(0, 2 * _PAD + 1, size=b)
        ox = rng.integers(0, 2 * _PAD + 1, size=b)
        flip = rng.random(b) < 0.5
        # a flipped crop that starts ox from the left of the image starts
        # 2*_PAD - ox from the left of its mirror image
        image = sel + n * flip
        left = np.where(flip, 2 * _PAD - ox, ox)
        src = (((image * hp + oy)[:, None] + down) * wp
               + left[:, None]).reshape(-1)
        out = batch_buffers.empty((b, h, w, c), x.dtype)
        rows = out.reshape(-1).view(run)
        fill_sliced(lambda lo, hi: _take_rows(runs, rows, src,
                                              lo * h, hi * h), b, slices)
        return out, arrays[1][sel]
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
    x = np.ascontiguousarray(x)
    aug = (_augment(np.random.default_rng(seed), x) if train and augment
           else None)
    ds = ArrayDataset((x, y), batch_size, shuffle=train, seed=seed,
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
