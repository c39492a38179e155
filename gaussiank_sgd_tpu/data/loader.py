"""Host-side batch iteration with background prefetch.

Reference parity: the torch ``DataLoader`` worker pool the reference leans on
(SURVEY.md §3.2 "io timer ← host dataloader workers"). One prefetch thread
per stream pulls whole batches into a bounded queue; the optional C++
pipeline (native/) slots in behind the same iterator protocol. The host
work is not small beside the accelerator's step, and what it costs is
memory traffic: a batch of 20 480 augmented CIFAR images is 252 MB of
float32, which four chips consume in 90 ms. So a large batch is assembled
in contiguous slices on a few worker threads (:func:`fill_sliced`; numpy
releases the GIL inside an indexed copy, so the slices run side by side),
and into a buffer that an earlier batch has left (:class:`BufferPool`): a
block that large comes from the allocator as fresh pages every time, which
the kernel has to find and zero while eight threads fault them in, and on
the chip's host that alone took as long as the copy (PERF.md, PR 35).

What the train loop can read of all this is on its ``data_wait`` span
(docs/OBSERVABILITY.md): ``ready``, the batches waiting in the queue when
the loop asked for one (:meth:`Prefetcher.ready`); ``assemble_ms``, what the
producer thread took for the newest batch it has pulled
(:attr:`Prefetcher.assemble_s`); ``fresh``, the buffers the pool has had to
allocate so far (:attr:`BufferPool.fresh`).

``ArrayDataset`` serves in-memory numpy arrays — both real files (CIFAR/PTB
fit comfortably in host RAM, as in the reference) and synthetic data.
"""

from __future__ import annotations

import collections
import math
import os
import queue
import threading
import time
import weakref
from typing import (Callable, Deque, Dict, Iterator, Optional, Sequence,
                    Tuple)

import numpy as np

# Errors the prefetch thread treats as transient and retries with bounded
# exponential backoff: the OSError family covers flaky disks/NFS/network
# (and chaos.TransientIOError subclasses it for tests). Anything else is a
# programming error and propagates immediately.
TRANSIENT_IO_ERRORS: Tuple[type, ...] = (OSError,)

# A batch is assembled in slices of at least _MIN_SLICE examples each (a
# thread has to have megabytes to copy before handing it work pays), on at
# most _MAX_SLICES threads: the caller and _MAX_SLICES - 1 daemon workers
# that every data set of the process shares.
_MIN_SLICE = 512
_MAX_SLICES = 8

_workers_lock = threading.Lock()
_worker_jobs: Optional["queue.SimpleQueue"] = None


def _slice_worker(jobs: "queue.SimpleQueue") -> None:
    """One worker thread: run each job's slice and report on the job's own
    ``done`` queue what it raised, or None."""
    while True:
        fill, lo, hi, done = jobs.get()
        try:
            fill(lo, hi)
        except BaseException as e:  # noqa: BLE001 — raised by fill_sliced
            done.put(e)
        else:
            done.put(None)


def _jobs() -> "queue.SimpleQueue":
    """The slice workers' job queue; the workers start at its first use."""
    global _worker_jobs
    with _workers_lock:
        if _worker_jobs is None:
            jobs: "queue.SimpleQueue" = queue.SimpleQueue()
            for i in range(_MAX_SLICES - 1):
                threading.Thread(target=_slice_worker, args=(jobs,),
                                 name=f"data-slice-{i}", daemon=True).start()
            _worker_jobs = jobs
        return _worker_jobs


def slice_count(n: int) -> int:
    """In how many slices a batch of ``n`` examples is assembled: what its
    size and the CPUs this process may run on allow, 1 (the calling thread
    alone) below ``2 * _MIN_SLICE`` examples."""
    return max(1, min(n // _MIN_SLICE, len(os.sched_getaffinity(0)),
                      _MAX_SLICES))


def fill_sliced(fill: Callable[[int, int], None], n: int,
                slices: Optional[int] = None) -> None:
    """Call ``fill(lo, hi)`` for contiguous slices that cover ``range(n)``:
    the first on the calling thread, the others on the worker threads, and
    return when all have ended. ``fill`` writes its part of an output the
    caller preallocated, so the result does not depend on ``slices``
    (default :func:`slice_count`; tests pass it). What a slice raises is
    raised here, after every slice has ended."""
    if slices is None:
        slices = slice_count(n)
    slices = max(1, min(slices, n))
    bounds = [n * i // slices for i in range(slices + 1)]
    done: "queue.SimpleQueue" = queue.SimpleQueue()
    if slices > 1:
        jobs = _jobs()
        for lo, hi in zip(bounds[1:-1], bounds[2:]):
            jobs.put((fill, lo, hi, done))
    try:
        fill(bounds[0], bounds[1])
    finally:
        errors = [done.get() for _ in range(slices - 1)]
    for e in errors:
        if e is not None:
            raise e


# Free buffers of one size that the pool keeps; one that returns to a full
# list pushes the oldest out to the allocator.
_KEEP_FREE = 8


class BufferPool:
    """Output arrays for batches, recycled: the memory of a batch that
    nothing can read any more is the memory of a later one.

    :meth:`empty` hands out an array as ``np.empty`` does. Its storage is
    bytes that ``np.empty`` gave (untouched: whoever fills them first pays
    for the pages, on as many threads as it fills them with), and it is a
    view of ``np.frombuffer`` over a ``memoryview`` of those: behind
    something that is no array, numpy lets the ``base`` of every further
    view, slice and reshape collapse to THAT array and no further, so a
    finalizer on it puts the storage back. So a buffer
    comes back when the batch, every view of it and whatever else holds one
    (``jax.device_put`` until its copy has ended; on the CPU backend the
    device array, which aliases it, for its life) have gone, and not
    before: no depth of any queue is assumed. The finalizer runs wherever
    the last reference dies, so it only appends to a deque; nothing here
    takes a lock that it could find held.
    """

    def __init__(self) -> None:
        self._free: Dict[int, Deque[np.ndarray]] = {}
        self._count = threading.Lock()
        self.fresh = 0      # buffers allocated so far: flat once they recycle

    def empty(self, shape: Sequence[int], dtype) -> np.ndarray:
        dtype = np.dtype(dtype)
        nbytes = math.prod(shape) * dtype.itemsize
        free = self._free.get(nbytes)
        if free is None:
            free = self._free.setdefault(
                nbytes, collections.deque(maxlen=_KEEP_FREE))
        try:
            raw = free.pop()
        except IndexError:
            raw = np.empty(nbytes, np.uint8)
            with self._count:
                self.fresh += 1
        flat = np.frombuffer(memoryview(raw), dtype)
        # nothing is left to recycle for at interpreter exit
        weakref.finalize(flat, free.append, raw).atexit = False
        return flat.reshape(shape)


# The process's one pool, as the slice workers are the process's: two
# trainers that take turns (the benchmark's arms) share what either left.
batch_buffers = BufferPool()


class ArrayDataset:
    """Shuffled, optionally-augmented minibatches over in-memory arrays.

    Yields tuples of numpy arrays with leading dim ``batch_size`` (drops the
    ragged tail, as the reference's samplers do for distributed training —
    every worker must see the same number of steps).

    ``augment(arrays, sel)`` assembles one batch from the data set's own
    arrays and the epoch's selection ``sel`` (so the gather is not a pass
    of its own); without it a batch is ``a[sel]`` of every array.
    """

    def __init__(self, arrays: Sequence[np.ndarray], batch_size: int,
                 shuffle: bool = True, seed: int = 0,
                 augment: Optional[Callable[..., tuple]] = None):
        lens = {len(a) for a in arrays}
        assert len(lens) == 1, f"ragged arrays: {lens}"
        self.arrays = tuple(arrays)
        self.batch_size = int(batch_size)
        self.shuffle = shuffle
        self.augment = augment
        self._rng = np.random.default_rng(seed)
        self.num_examples = len(arrays[0])
        self.steps_per_epoch = self.num_examples // self.batch_size
        assert self.steps_per_epoch > 0, (
            f"batch_size {batch_size} > dataset size {self.num_examples}")

    def epoch(self, epoch_seed: Optional[int] = None) -> Iterator[tuple]:
        order = np.arange(self.num_examples)
        if self.shuffle:
            rng = (np.random.default_rng(epoch_seed) if epoch_seed is not None
                   else self._rng)
            rng.shuffle(order)
        for s in range(self.steps_per_epoch):
            sel = order[s * self.batch_size:(s + 1) * self.batch_size]
            if self.augment is not None:
                yield self.augment(self.arrays, sel)
            else:
                yield tuple(a[sel] for a in self.arrays)

    def __iter__(self):
        while True:  # epoch-looping stream
            yield from self.epoch()


class BucketedDataset:
    """Batches from length-homogeneous pools (quantized length bucketing).

    Reference parity: the DeepSpeech-style similar-length BucketingSampler
    behind the AN4 workload (SURVEY.md §2 C9), reshaped for XLA: each pool
    holds utterances padded to ONE static frame width, so every batch has
    one of a handful of fixed shapes (one compile per width) instead of a
    ragged shape per batch. An epoch interleaves pool batches in shuffled
    order; every pool finishes exactly once per epoch.
    """

    def __init__(self, pools: Sequence[ArrayDataset], seed: int = 0):
        assert pools
        self.pools = list(pools)
        self.batch_size = pools[0].batch_size
        self.steps_per_epoch = sum(p.steps_per_epoch for p in pools)
        self.num_examples = sum(p.num_examples for p in pools)
        self._rng = np.random.default_rng(seed)

    def epoch(self, epoch_seed: Optional[int] = None) -> Iterator[tuple]:
        rng = (np.random.default_rng(epoch_seed) if epoch_seed is not None
               else self._rng)
        schedule = np.repeat(np.arange(len(self.pools)),
                             [p.steps_per_epoch for p in self.pools])
        rng.shuffle(schedule)
        iters = [p.epoch(epoch_seed=epoch_seed) for p in self.pools]
        for i in schedule:
            yield next(iters[i])

    def __iter__(self):
        while True:
            yield from self.epoch()


class EpochStream:
    """Resumable epoch-looping batch stream aligned to a global step.

    The iterator-protocol twin of ``while True: yield from
    ds.epoch(epoch_seed=seed + ep)``, written as a class so
    :func:`prefetch`'s transient-IO retry actually works on the training
    path: an error raised by the underlying dataset propagates to the
    caller but leaves THIS iterator alive — the next ``__next__`` rebuilds
    the (now-finalized) epoch iterator and fast-forwards to the failed
    position, re-attempting the same batch. A generator here would be
    finalized by the first raise, turning every retry into StopIteration
    — i.e. a silent end of the infinite stream.

    Alignment: construction at global step ``start_step`` positions the
    stream exactly where an uninterrupted run would be — epoch
    ``start_step // steps_per_epoch``, shuffled with ``seed + epoch``,
    offset ``start_step % steps_per_epoch`` — the exact data-iterator
    resume contract (SURVEY.md §5 checkpoint rebuild note). ``ds`` needs
    ``steps_per_epoch`` and ``epoch(epoch_seed=...)``, which every
    pipeline class provides.
    """

    def __init__(self, ds, seed: int, start_step: int = 0):
        self._ds = ds
        self._seed = int(seed)
        self._epoch = start_step // ds.steps_per_epoch
        self._pos = start_step % ds.steps_per_epoch  # next batch index
        self._it: Optional[Iterator] = None
        self._it_pos = 0            # batches consumed from the live _it

    def __iter__(self) -> "EpochStream":
        return self

    def __next__(self):
        while True:
            if self._it is None:
                self._it = self._ds.epoch(
                    epoch_seed=self._seed + self._epoch)
                self._it_pos = 0
            try:
                # steady state runs this loop once (_it_pos == _pos); after
                # an error or a resume it replays the deterministic epoch
                # up to the target position first
                while True:
                    batch = next(self._it)
                    self._it_pos += 1
                    if self._it_pos > self._pos:
                        break
            except StopIteration:
                self._epoch += 1
                self._pos = 0
                self._it = None
                continue
            except BaseException:
                # the raise finalized the underlying epoch generator; drop
                # it so the next attempt (prefetch retry) rebuilds and
                # fast-forwards back to this same position
                self._it = None
                raise
            self._pos += 1
            return batch


class Prefetcher:
    """The iterator :func:`prefetch` returns: the batches of its source,
    pulled by a daemon thread; ``ready()``, how many of them wait in its
    queue right now (0 to ``depth``); and ``assemble_s``, the seconds the
    thread spent in the pull of the newest batch it has pulled, retries and
    whatever the source itself waited for included (None before the
    first). The train loop records both on its ``data_wait`` span (fields
    ``ready`` and ``assemble_ms``): a queue that runs empty says THAT the
    loop waits for this thread, ``assemble_s`` against the step's time says
    by how much the source would have to be faster."""

    def __init__(self, it: Iterator, depth: int, *retry):
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self.depth = depth
        self.assemble_s: Optional[float] = None
        self._batches = _prefetched(it, self, *retry)

    def __iter__(self) -> "Prefetcher":
        return self

    def __next__(self):
        return next(self._batches)

    def ready(self) -> int:
        return self._q.qsize()


def prefetch(it: Iterator, depth: int = 2, max_retries: int = 0,
             backoff_s: float = 0.05, max_backoff_s: float = 2.0,
             on_event: Optional[Callable[[dict], None]] = None,
             ) -> Prefetcher:
    """Run ``it`` in a daemon thread, keeping ``depth`` batches ready.

    Overlaps host batch prep with device compute — the role of the
    reference's DataLoader workers. The thread keeps the device fed only
    while ``it`` yields a batch in less time than a step takes:
    ``Prefetcher.ready()`` near ``depth`` says it does, near 0 that the
    train loop waits for this thread (``vgg16_dp1`` read 0.011 while
    batches were cropped image by image, PERF.md). Sources whose batches
    are large assemble them in slices on the package's worker threads
    (:func:`fill_sliced`), under this one thread's ``next()``.

    ``max_retries`` > 0 adds transient-fault tolerance: a pull that raises
    one of :data:`TRANSIENT_IO_ERRORS` is retried up to ``max_retries``
    times with bounded exponential backoff (``backoff_s * 2**attempt``,
    capped at ``max_backoff_s``), then propagates. Retry needs a
    *resumable* source (a class-based iterator such as :class:`EpochStream`
    — the Trainer's production stream); a generator is finalized by its
    first raise, so its retries hit StopIteration — that StopIteration is
    recognized (the pull DID fail) and the original transient error is
    re-raised instead of silently ending the stream. Each attempt emits an
    ``{"event": "io_retry", ...}`` record through ``on_event`` (the
    Trainer wires this to its telemetry EventBus, which stamps the
    schema/seq envelope); ``on_event`` runs on the prefetch thread, so
    the sink must be thread-safe (telemetry.EventBus.publish is).
    """
    return Prefetcher(it, depth, max_retries, backoff_s, max_backoff_s,
                      on_event)


def _prefetched(it: Iterator, out: Prefetcher, max_retries: int,
                backoff_s: float, max_backoff_s: float,
                on_event: Optional[Callable[[dict], None]]) -> Iterator:
    """The generator behind :func:`prefetch`: starts the producer thread
    at its first pull and hands on what the thread queued."""
    q = out._q
    _END = object()
    _ERR = object()

    def pull(src: Iterator):
        attempt = 0
        last_err: Optional[BaseException] = None
        while True:
            try:
                return next(src)
            except StopIteration:
                if last_err is not None:
                    # a generator source was finalized by the transient
                    # error it raised; its "end" IS the failure — re-raise
                    # the real cause instead of letting the infinite
                    # stream silently end as a clean StopIteration
                    raise last_err
                raise
            except TRANSIENT_IO_ERRORS as e:
                last_err = e
                attempt += 1
                if attempt > max_retries:
                    raise
                delay = min(backoff_s * (2.0 ** (attempt - 1)),
                            max_backoff_s)
                if on_event is not None:
                    on_event({"event": "io_retry", "attempt": attempt,
                              "max_retries": max_retries,
                              "backoff_s": round(delay, 6),
                              "error": repr(e)})
                time.sleep(delay)

    def worker():
        try:
            src = iter(it)
            while True:
                t0 = time.perf_counter()
                try:
                    item = pull(src)
                except StopIteration:
                    q.put(_END)
                    return
                out.assemble_s = time.perf_counter() - t0
                q.put(item)
        except BaseException as e:  # noqa: BLE001 — re-raised in consumer
            q.put((_ERR, e))

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    while True:
        item = q.get()
        if item is _END:
            return
        if isinstance(item, tuple) and len(item) == 2 and item[0] is _ERR:
            raise RuntimeError("data prefetch thread failed") from item[1]
        yield item
