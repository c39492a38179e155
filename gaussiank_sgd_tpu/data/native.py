"""ctypes bindings for the native host-pipeline library (native/).

Role (SURVEY.md §2.1): the TPU-native replacement for the torch DataLoader
C++ worker pool the reference depends on. The library is built lazily with
g++ the first time it is requested (cached under native/build/); every entry
point degrades to the pure-numpy implementations in this package when the
toolchain or build is unavailable, so the framework never *requires* the
native path.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

import numpy as np

_REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                          "..", ".."))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "build", "libgksgd_io.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build() -> bool:
    src = os.path.join(_NATIVE_DIR, "io_pipeline.cpp")
    if not os.path.exists(src):
        return False
    os.makedirs(os.path.dirname(_LIB_PATH), exist_ok=True)
    # no -march=native: native/build/ is git-ignored but travels with a
    # copy of the tree, and a library tuned to the build host's CPU dies
    # with SIGILL (no traceback) in the loader of any other
    cmd = ["g++", "-O3", "-fPIC", "-std=c++17", "-shared",
           "-pthread", "-o", _LIB_PATH, src]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        return True
    except Exception:
        return False


def _stale() -> bool:
    """The cached .so predates the current source (e.g. a symbol was added)."""
    src = os.path.join(_NATIVE_DIR, "io_pipeline.cpp")
    try:
        return os.path.getmtime(src) > os.path.getmtime(_LIB_PATH)
    except OSError:
        return False


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    """Declare every entry point; raises AttributeError on a stale .so."""
    u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
    i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
    f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
    lib.gk_assemble_batch.argtypes = [
        u8p, i32p, i32p,
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, f32p, f32p, f32p, i32p,
        ctypes.c_uint64, ctypes.c_int, ctypes.c_int]
    lib.gk_assemble_batch.restype = None
    lib.gk_shuffle_indices.argtypes = [i32p, ctypes.c_int, ctypes.c_uint64]
    lib.gk_shuffle_indices.restype = None
    lib.gk_log_spectrogram.argtypes = [f32p, ctypes.c_int, ctypes.c_int,
                                       ctypes.c_int, f32p, ctypes.c_int]
    lib.gk_log_spectrogram.restype = None
    return lib


def load() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None if unavailable."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if ((not os.path.exists(_LIB_PATH) or _stale()) and not _build()):
            return None
        for attempt in range(2):
            try:
                _lib = _bind(ctypes.CDLL(_LIB_PATH))
                return _lib
            except OSError:
                return None
            except AttributeError:
                # stale cached .so missing a newer symbol: rebuild once,
                # then degrade to the numpy fallbacks (module contract)
                if attempt or not _build():
                    return None
        return None


def available() -> bool:
    return load() is not None


def assemble_batch(images_u8: np.ndarray, labels: np.ndarray,
                   sel: np.ndarray, mean: np.ndarray, std: np.ndarray,
                   seed: int, augment: bool, pad: int = 4,
                   nthreads: int = 4):
    """Gather+normalize+augment a batch natively. Caller checks available()."""
    lib = load()
    assert lib is not None
    b = int(sel.shape[0])
    h, w, c = images_u8.shape[1:]
    out_x = np.empty((b, h, w, c), np.float32)
    out_y = np.empty((b,), np.int32)
    lib.gk_assemble_batch(
        np.ascontiguousarray(images_u8), np.ascontiguousarray(labels),
        np.ascontiguousarray(sel.astype(np.int32)), b, h, w, c, pad,
        np.ascontiguousarray(mean.astype(np.float32)),
        np.ascontiguousarray(std.astype(np.float32)),
        out_x, out_y, ctypes.c_uint64(seed & (2**64 - 1)),
        1 if augment else 0, nthreads)
    return out_x, out_y


def log_spectrogram(samples: np.ndarray, n_fft: int, stride: int,
                    nthreads: int = 4) -> np.ndarray:
    """Native STFT log-magnitude features: [n_freq, n_frames] (un-normalized;
    caller applies mean/std). Caller checks available()."""
    lib = load()
    assert lib is not None
    samples = np.ascontiguousarray(samples, np.float32)
    assert len(samples) >= n_fft, (
        f"need >= n_fft={n_fft} samples, got {len(samples)} (pad first)")
    n_freq = n_fft // 2 + 1
    n_frames = 1 + (len(samples) - n_fft) // stride
    out = np.empty((n_freq, n_frames), np.float32)
    lib.gk_log_spectrogram(samples, len(samples), n_fft, stride, out,
                           nthreads)
    return out


def shuffle_indices(n: int, seed: int) -> np.ndarray:
    lib = load()
    assert lib is not None
    idx = np.empty((n,), np.int32)
    lib.gk_shuffle_indices(idx, n, ctypes.c_uint64(seed & (2**64 - 1)))
    return idx
