"""Data pipeline tests (SURVEY.md §2 C5 pipelines, offline synthetic mode)."""

import numpy as np
import pytest

from gaussiank_sgd_tpu.data import (make_dataset, prefetch)
from gaussiank_sgd_tpu.data.loader import ArrayDataset
from gaussiank_sgd_tpu.data.synthetic import (synthetic_images,
                                              synthetic_tokens)


def test_array_dataset_batching_and_shuffle():
    x = np.arange(100, dtype=np.float32)[:, None]
    y = np.arange(100, dtype=np.int32)
    ds = ArrayDataset((x, y), batch_size=16, shuffle=True, seed=0)
    assert ds.steps_per_epoch == 6
    b = list(ds.epoch())
    assert len(b) == 6
    seen = np.concatenate([yy for _, yy in b])
    assert len(set(seen.tolist())) == 96  # no duplicates within an epoch
    # alignment: label must match the value stored in x
    for xx, yy in b:
        np.testing.assert_array_equal(xx[:, 0].astype(np.int32), yy)


def test_cifar_synthetic_pipeline():
    ds, nc = make_dataset("cifar10", data_dir=None, batch_size=32)
    assert nc == 10
    x, y = next(iter(ds))
    assert x.shape == (32, 32, 32, 3) and x.dtype == np.float32
    assert y.shape == (32,) and y.dtype == np.int32
    assert 0 <= y.min() and y.max() < 10


def test_cifar_augmentation_changes_pixels_not_labels():
    ds, _ = make_dataset("cifar10", batch_size=16, augment=True)
    ds2, _ = make_dataset("cifar10", batch_size=16, augment=False)
    (xa, ya), (xb, yb) = next(ds.epoch(epoch_seed=5)), next(
        ds2.epoch(epoch_seed=5))
    np.testing.assert_array_equal(ya, yb)
    assert not np.allclose(xa, xb)


def test_ptb_windows_are_shifted_by_one():
    ds, vocab = make_dataset("ptb", batch_size=4, bptt=10)
    x, y = next(iter(ds))
    assert x.shape == (4, 10) and y.shape == (4, 10)
    # y is x shifted: the stream property x[t+1] == y[t]
    np.testing.assert_array_equal(x[:, 1:], y[:, :-1])
    assert vocab == 10000


def test_synthetic_images_learnable_signal():
    x, y = synthetic_images(512, (8, 8, 1), 4, seed=0)
    # nearest-template classification should be near perfect
    templates = np.stack([x[y == c].mean(0) for c in range(4)])
    pred = np.argmin(((x[:, None] - templates[None]) ** 2).sum((2, 3, 4)), 1)
    assert (pred == y).mean() > 0.95


def test_wmt_and_an4_shapes():
    ds, v = make_dataset("wmt14", batch_size=8, src_len=16, tgt_len=16,
                         vocab_size=100, synthetic_examples=64)
    s, t = next(iter(ds))
    assert s.shape == (8, 16) and t.shape == (8, 16) and v == 100
    ds, nl = make_dataset("an4", batch_size=4, synthetic_examples=16)
    x, lab = next(iter(ds))
    assert x.shape == (4, 161, 200) and lab.shape == (4, 8) and nl == 29


def test_prefetch_preserves_order_and_count():
    ds = ArrayDataset((np.arange(64)[:, None],), 8, shuffle=False)
    direct = [b[0][0, 0] for b in ds.epoch()]
    pre = [b[0][0, 0] for b in prefetch(ds.epoch(), depth=3)]
    assert direct == pre and len(pre) == 8


def test_markov_tokens_are_predictable():
    toks = synthetic_tokens(50_000, 100, seed=0)
    # bigram model should beat uniform by a lot (learnability check)
    from collections import Counter, defaultdict
    nxt = defaultdict(Counter)
    for a, b in zip(toks[:-1], toks[1:]):
        nxt[a][b] += 1
    correct = sum(nxt[a].most_common(1)[0][1] for a in nxt)
    acc = correct / (len(toks) - 1)
    assert acc > 0.2, acc  # uniform would be 0.01


def test_imagenet_u8_pipeline_and_device_normalize():
    """The imagenet contract ships uint8 pixels (4x less transfer) and the
    loss normalizes on device (training/losses.py _prep_pixels)."""
    import jax.numpy as jnp

    from gaussiank_sgd_tpu.data import make_imagenet
    from gaussiank_sgd_tpu.training.losses import IMAGENET_NORM, _prep_pixels

    ds, ncls = make_imagenet(None, train=True, batch_size=8, image_size=32,
                             synthetic_examples=64)
    x, y = next(iter(ds))
    assert x.dtype == np.uint8 and x.shape == (8, 32, 32, 3)
    assert ncls == 1000
    xn = _prep_pixels(jnp.asarray(x), IMAGENET_NORM)
    assert xn.dtype == jnp.float32
    # normalized stats land in the standard range (mean ~0, |x| < ~3)
    assert abs(float(xn.mean())) < 1.0
    assert float(jnp.abs(xn).max()) < 4.0
    # float inputs pass through untouched (static dtype check)
    xf = jnp.ones((2, 4, 4, 3), jnp.float32) * 7.0
    np.testing.assert_array_equal(np.asarray(_prep_pixels(xf, IMAGENET_NORM)),
                                  np.asarray(xf))


def test_label_noise_caps_ceiling():
    """flip_labels: ~fraction of labels change, none to the same class."""
    from gaussiank_sgd_tpu.data import flip_labels

    y = np.random.default_rng(0).integers(0, 10, 10_000).astype(np.int32)
    y2 = flip_labels(y, 10, 0.25, seed=3)
    frac = float((y != y2).mean())
    assert 0.20 < frac < 0.30, frac
    assert y2.min() >= 0 and y2.max() < 10
    np.testing.assert_array_equal(y, flip_labels(y, 10, 0.0, seed=3))


# ---- batch assembly in slices (data/loader.fill_sliced, data/cifar._augment)

def _old_augment(rng):
    """The assembly before the sliced one, kept as the oracle: gather (by
    the caller), reflect-pad the whole batch, crop image by image, flip."""
    def fn(x, y):
        b, h, w, c = x.shape
        padded = np.pad(x, ((0, 0), (4, 4), (4, 4), (0, 0)), mode="reflect")
        oy = rng.integers(0, 9, size=b)
        ox = rng.integers(0, 9, size=b)
        out = np.empty_like(x)
        for i in range(b):
            out[i] = padded[i, oy[i]:oy[i] + h, ox[i]:ox[i] + w]
        flip = rng.random(b) < 0.5
        out[flip] = out[flip, :, ::-1]
        return out, y
    return fn


def _old_dataset(arrays, batch_size, seed):
    """What ``make_cifar`` builds over ``arrays``, behind the old assembly."""
    old = _old_augment(np.random.default_rng(seed))
    return ArrayDataset(
        arrays, batch_size, shuffle=True, seed=seed,
        augment=lambda arrays, sel: old(*(a[sel] for a in arrays)))


@pytest.fixture(scope="module")
def cifar_arrays():
    """(x, y) of the synthetic CIFAR stand-ins, made once a name."""
    made = {}

    def get(name):
        if name not in made:
            ds, _ = make_dataset(name, batch_size=16,
                                 synthetic_examples=6144)
            made[name] = ds.arrays
        return made[name]
    return get


def _assert_batches_equal(got, want):
    for g, w in zip(got, want, strict=True):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("slices", [1, 3, 8])
@pytest.mark.parametrize("name", ["cifar10", "cifar100"])
@pytest.mark.parametrize("batch", [16, 128, 300, 1031, 5120])
def test_cifar_assembly_equals_the_old_loop_bit_for_bit(
        cifar_arrays, batch, name, slices):
    """Same generator state in, same batch out, whatever the slice count
    (1031 is prime: no slice count divides it; 300 images are one indexed
    read of ``_ROWS_AT_ONCE`` rows and a shorter one)."""
    from gaussiank_sgd_tpu.data.cifar import _augment

    x, y = cifar_arrays(name)
    sel = np.random.default_rng(batch).permutation(len(x))[:batch]
    new_rng, old_rng = np.random.default_rng(11), np.random.default_rng(11)
    got = _augment(new_rng, x)((x, y), sel, slices=slices)
    want = _old_augment(old_rng)(x[sel], y[sel])
    _assert_batches_equal(got, want)
    assert got[0].dtype == np.float32 and got[0].flags.c_contiguous
    # and the generator was consumed as the old loop consumed it
    assert new_rng.random() == old_rng.random()


@pytest.mark.parametrize("cpus,n,want", [
    (8, 16, 1), (8, 128, 1), (8, 1023, 1), (8, 1024, 2), (8, 5120, 8),
    (13, 20480, 8), (3, 5120, 3), (1, 5120, 1)])
def test_slice_count_follows_batch_size_and_cpus(monkeypatch, cpus, n, want):
    from gaussiank_sgd_tpu.data import loader

    monkeypatch.setattr(loader.os, "sched_getaffinity",
                        lambda pid: set(range(cpus)))
    assert loader.slice_count(n) == want


@pytest.mark.parametrize("n,slices", [(1, 8), (7, 3), (1031, 8), (4096, 1),
                                      (5120, None)])
def test_fill_sliced_covers_the_range_once(n, slices):
    from gaussiank_sgd_tpu.data.loader import fill_sliced

    hits = np.zeros(n, np.int32)
    spans = []

    def fill(lo, hi):
        assert lo < hi
        hits[lo:hi] += 1
        spans.append((lo, hi))
    fill_sliced(fill, n, slices)
    assert (hits == 1).all()
    if slices is not None:
        assert len(spans) == min(slices, n)


def test_cifar_two_epochs_consume_the_generator_as_before():
    """steps_per_epoch is 2, so the third batch is the next epoch's first:
    shuffle and augmentation draws go on as with the old assembly (1536
    images are assembled in slices wherever there are three CPUs)."""
    ds, _ = make_dataset("cifar10", batch_size=1536, seed=5,
                         synthetic_examples=3300)
    assert ds.steps_per_epoch == 2
    old = _old_dataset(ds.arrays, ds.batch_size, seed=5)
    for _, got, want in zip(range(3), ds, old):
        _assert_batches_equal(got, want)


def test_epoch_stream_resumes_on_the_augmented_batch():
    """EpochStream(ds, seed, start_step=k) replays the epoch up to k, so a
    fresh data set yields what an uninterrupted stream yields at step k,
    crop offsets and flips included."""
    from gaussiank_sgd_tpu.data import EpochStream

    def fresh():
        return make_dataset("cifar100", batch_size=1100, seed=2,
                            synthetic_examples=4500)[0]
    k = 2
    straight = EpochStream(fresh(), seed=9)
    for _ in range(k):
        next(straight)
    resumed = next(EpochStream(fresh(), seed=9, start_step=k))
    _assert_batches_equal(resumed, next(straight))
    # and both are the old assembly's batch at that step
    ds = fresh()
    old = EpochStream(_old_dataset(ds.arrays, ds.batch_size, seed=2),
                      seed=9, start_step=k)
    _assert_batches_equal(resumed, next(old))


def test_slice_failure_reaches_the_consumer_through_prefetch(monkeypatch):
    """What one slice raises on a worker thread comes out of the prefetch
    thread as every other producer failure does."""
    from gaussiank_sgd_tpu.data import EpochStream, cifar
    from gaussiank_sgd_tpu.data.loader import fill_sliced

    take = cifar._take_rows

    def failing(runs, rows, src, lo, hi):
        if lo > 0:
            raise ValueError(f"slice {lo}:{hi} broke")
        take(runs, rows, src, lo, hi)
    monkeypatch.setattr(cifar, "_take_rows", failing)
    # three slices whatever this machine's CPUs would allow a batch of 64
    monkeypatch.setattr(cifar, "fill_sliced",
                        lambda fill, n, slices=None: fill_sliced(fill, n, 3))
    ds, _ = make_dataset("cifar10", batch_size=64, synthetic_examples=256)
    it = prefetch(EpochStream(ds, seed=0))
    with pytest.raises(RuntimeError, match="data prefetch thread failed") \
            as err:
        next(it)
    assert isinstance(err.value.__cause__, ValueError)
    assert "broke" in str(err.value.__cause__)


def test_two_datasets_on_two_threads_give_what_each_gives_alone():
    """The benchmark's two trainers share the slice workers: batches drawn
    from two data sets at once are the ones each yields alone."""
    import sys
    import threading

    def fresh(seed):
        return make_dataset("cifar10", batch_size=2048, seed=seed,
                            synthetic_examples=4200)[0]
    n_batches = 5
    alone = {seed: [b for _, b in zip(range(n_batches), fresh(seed))]
             for seed in (1, 2)}
    together = {1: [], 2: []}

    def drive(seed):
        for _, b in zip(range(n_batches), fresh(seed)):
            together[seed].append(b)
    threads = [threading.Thread(target=drive, args=(seed,), daemon=True)
               for seed in (1, 2)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    for seed in (1, 2):
        assert len(together[seed]) == n_batches
        for got, want in zip(together[seed], alone[seed]):
            _assert_batches_equal(got, want)


def test_cifar_files_without_the_native_library_assemble_as_before(tmp_path):
    """Real records come channel-first, and ``_normalize`` keeps that
    memory order under its NHWC shape: the assembly reads rows of pixels by
    their place in memory, so make_cifar has to lay the images out first.
    The batch is the old path's on the same records."""
    from gaussiank_sgd_tpu.data import cifar

    rng = np.random.default_rng(3)
    for i in range(1, 6):
        rec = rng.integers(0, 256, size=(24, 3073), dtype=np.uint8)
        rec[:, 0] %= 10
        rec.tofile(tmp_path / f"data_batch_{i}.bin")
    ds, _ = cifar.make_cifar("cifar10", str(tmp_path), batch_size=50,
                             seed=4, use_native=False)
    x_u8, y = cifar._read_cifar10_bin(str(tmp_path), True)
    x = cifar._normalize(x_u8)
    assert not x.flags.c_contiguous and ds.arrays[0].flags.c_contiguous
    for _, got, exp in zip(range(3), ds, _old_dataset((x, y), 50, seed=4)):
        _assert_batches_equal(got, exp)


# ---- recycled batch buffers (data/loader.BufferPool)

@pytest.fixture
def pool(monkeypatch):
    """A pool of this test's own in place of the process's."""
    from gaussiank_sgd_tpu.data import cifar, loader

    own = loader.BufferPool()
    monkeypatch.setattr(cifar, "batch_buffers", own)
    return own


def _cifar_and_oracle(batch_size, seed, examples):
    ds, _ = make_dataset("cifar10", batch_size=batch_size, seed=seed,
                         synthetic_examples=examples)
    return ds, _old_dataset(ds.arrays, batch_size, seed=seed)


@pytest.mark.parametrize("shape,dtype", [((7, 32, 32, 3), np.float32),
                                         ((5, 3), np.int64), ((4,), "u1")])
def test_pool_hands_out_what_np_empty_hands_out(shape, dtype):
    from gaussiank_sgd_tpu.data.loader import BufferPool

    a = BufferPool().empty(shape, dtype)
    assert a.shape == shape and a.dtype == np.dtype(dtype)
    assert a.flags.c_contiguous and a.flags.writeable and a.flags.aligned
    a[...] = 3
    assert (a == 3).all()


def test_pool_recycles_by_size_and_keeps_a_bounded_list():
    from gaussiank_sgd_tpu.data import loader

    own = loader.BufferPool()
    a = own.empty((6, 4), np.float32)
    at = a.ctypes.data
    small = own.empty((5, 4), np.float32)      # another size: another list
    assert own.fresh == 2
    del a
    assert own.empty((2, 12), np.float32).ctypes.data == at     # 96 bytes
    assert own.fresh == 2
    del small
    # more buffers come back than the list keeps: the oldest go
    held = [own.empty((3,), np.float64) for _ in range(loader._KEEP_FREE + 3)]
    assert own.fresh == 2 + len(held)
    del held
    again = [own.empty((3,), np.float64) for _ in range(loader._KEEP_FREE + 3)]
    assert own.fresh == 2 + len(again) + 3


def test_a_dropped_batch_is_the_next_batchs_memory(pool):
    """The consumer holds one batch at a time: two buffers are all the
    pool ever allocates (the generator makes the next before the last is
    let go), and they take turns."""
    ds, old = _cifar_and_oracle(64, seed=3, examples=256)
    seen = []
    for _, got, want in zip(range(12), ds, old):
        _assert_batches_equal(got, want)
        seen.append(got[0].ctypes.data)
        del got
    assert pool.fresh == 2
    assert len(set(seen)) == 2 and seen[2:] == seen[:-2]


@pytest.mark.parametrize("hold", ["batch", "slice", "reshape_of_slice"])
def test_what_is_held_keeps_its_values_while_the_pool_recycles(pool, hold):
    """A buffer comes back when the batch AND every view of it are gone:
    a slice, and a reshape of a slice (whose ``base`` is not the batch),
    read the same after ten further batches."""
    ds, old = _cifar_and_oracle(48, seed=6, examples=200)
    it, old_it = iter(ds), iter(old)
    x, want = next(it)[0], next(old_it)[0]
    if hold != "batch":
        x, want = x[5:29], want[5:29]
    if hold == "reshape_of_slice":
        x, want = x.reshape(24, -1), want.reshape(24, -1)
    for _, got, exp in zip(range(10), it, old_it):
        _assert_batches_equal(got, exp)
        del got
    np.testing.assert_array_equal(x, want)
    assert pool.fresh == 3          # the held one, and two that take turns


def test_a_list_of_an_epoch_is_distinct_batches(pool):
    ds, old = _cifar_and_oracle(40, seed=8, examples=200)
    assert ds.steps_per_epoch == 5
    got = list(ds.epoch(epoch_seed=4))
    want = list(old.epoch(epoch_seed=4))
    assert len({g[0].ctypes.data for g in got}) == 5 and pool.fresh == 5
    for g, w in zip(got, want, strict=True):
        _assert_batches_equal(g, w)


def test_device_put_and_drop_reads_the_oracle_on_the_device(pool):
    """The train loop's use: each batch is placed and the host array let
    go at once. A placed array (on the CPU backend it may alias the host's)
    holds what the oracle holds, now and after twenty further batches."""
    import jax

    ds, old = _cifar_and_oracle(96, seed=12, examples=400)
    placed, wanted = [], []
    for _, got, want in zip(range(20), ds, old):
        placed.append(jax.device_put(got[0]))
        wanted.append(want[0])
        del got
        np.testing.assert_array_equal(np.asarray(placed[-1]), wanted[-1])
    for dev, want in zip(placed, wanted, strict=True):
        np.testing.assert_array_equal(np.asarray(dev), want)


def test_prefetch_times_the_pull_of_its_newest_batch():
    import time

    def slow():
        for i in range(3):
            time.sleep(0.02)
            yield i
    it = prefetch(slow())
    assert it.assemble_s is None        # the thread starts at the first pull
    assert list(it) == [0, 1, 2]
    assert 0.02 <= it.assemble_s < 2.0
