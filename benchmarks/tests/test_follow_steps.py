"""`reference/common.py` `follow_steps`, which PR 26 rewrote to hold a
bounded share of the chip and PR 27 to do the host's arithmetic in blocks on
threads, the next worker's gradient call running meanwhile: against the
implementation up to PR 25 (kept here, word for word), bit for bit; and
what is alive on the device while it runs."""

import sys


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness
from benchmarks.reference import common as C


def follow_steps_before_pr26(loss_fn, params: dict, shards, masks, *, lrs,
                             momentum, weight_decay, probe=None):
    """The implementation up to PR 25: every vector on the device, the
    parameters flat AND as a tree, each worker's first gradient kept there.
    (`probe` added: the same two places are reported.)"""
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    p = C.flatten(params)
    m = jnp.zeros_like(p)
    nworkers = len(shards[0])
    residual = [jnp.zeros_like(p) for _ in range(nworkers)]
    losses, first_grad, first_grads = [], None, []
    for s, step_shards in enumerate(shards):
        tree = C.unflatten(p, params)
        G = jnp.zeros_like(p)
        gsum = jnp.zeros_like(p)
        loss = 0.0
        for w, batch in enumerate(step_shards):
            l, g = grad_fn(tree, batch)
            if probe is not None:
                probe("grad_returned")
            g = C.flatten(g)
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
        if probe is not None:
            probe("step_end")
    return {"losses": losses, "first_grad": first_grad, "params": p,
            "first_grad_workers": first_grads}


# ------------------------------------------- the throw-away configuration

@pytest.fixture(scope="module")
def tiny(tiny_root):
    """The tests' tiny VGG: its reference loss (batch norm over the
    worker's batch, dropout), seeded weights, three steps of rows for four
    workers, and masks that send about one entry in twenty."""
    cell = harness.load_cell("tiny_dp4", root=tiny_root)
    config = cell["config_data"]
    ref = harness.load_reference(config)
    params = {p: np.asarray(v) for p, v in jax.jit(
        lambda k: ref.init_params(k, config))(jax.random.PRNGKey(11)).items()}
    n = sum(v.size for v in params.values())
    rng = np.random.default_rng(5)
    per, width = 8, config["dropout"]["width"]

    def batch():
        keep = (rng.random((per, width)) < 0.5).astype(np.float32) * 2.0
        return (rng.standard_normal((per, 32, 32, 3)).astype(np.float32),
                rng.integers(0, 10, (per,)).astype(np.int32), keep)

    shards = [[batch() for _ in range(4)] for _ in range(3)]
    masks = [[rng.random(n) < 0.05 for _ in range(4)] for _ in range(3)]

    def loss_fn(p, b):
        return ref.loss(p, b, config, "float32")

    return loss_fn, params, shards, masks, n


@pytest.mark.parametrize("threads", [1, 8])
@pytest.mark.parametrize("workers", [1, 4])
@pytest.mark.parametrize("arm", ["sparse", "dense"])
def test_the_new_follow_steps_reads_what_the_old_one_read(tiny, workers, arm,
                                                          threads,
                                                          monkeypatch):
    """Bit for bit: the step's arithmetic is IEEE float32 add, multiply and
    divide in the same order, in numpy on the host where it was XLA's on
    the device one operation at a time, and the gradient program is the
    same program. The host takes the vectors a chunk at a time: here in
    sixteen chunks, the last one short, on one thread and on eight."""
    loss_fn, params, shards, masks, n = tiny
    monkeypatch.setattr(C, "_CHUNK", 4099)
    monkeypatch.setattr(C, "_THREADS", threads)
    assert n // 4099 == 15 and n % 4099
    shards = [row[:workers] for row in shards]
    masks = ([row[:workers] for row in masks] if arm == "sparse"
             else [None] * len(shards))
    kw = dict(lrs=[0.05, 0.06, 0.07], momentum=0.9, weight_decay=5e-4)
    old = follow_steps_before_pr26(
        loss_fn, {k: jnp.asarray(v) for k, v in params.items()},
        [[tuple(jnp.asarray(a) for a in b) for b in row] for row in shards],
        [None if row is None else [jnp.asarray(x) for x in row]
         for row in masks], **kw)
    new = C.follow_steps(loss_fn, params, shards, masks, **kw)
    assert set(new) == set(old)
    assert new["losses"] == old["losses"]
    for key in ("first_grad", "params"):
        assert new[key].dtype == np.float32 and new[key].shape == (n,)
        np.testing.assert_array_equal(new[key], np.asarray(old[key]), key)
    assert len(new["first_grad_workers"]) == workers
    for a, b in zip(new["first_grad_workers"], old["first_grad_workers"]):
        np.testing.assert_array_equal(a, np.asarray(b))
    # and it moved: a comparison of two vectors that stood still is none
    assert np.abs(new["params"] - C.flatten(
        {k: jnp.asarray(v) for k, v in params.items()})).max() > 1e-4


def test_more_threads_than_cores_change_no_bit(tiny, monkeypatch):
    """The threads write disjoint chunks of the shared vectors and nothing
    else: thirty-two of them on 251-element chunks, the interpreter
    switching between them as often as it can, read what one thread
    reads."""
    loss_fn, params, shards, masks, n = tiny
    kw = dict(lrs=[0.05, 0.06, 0.07], momentum=0.9, weight_decay=5e-4)
    monkeypatch.setattr(C, "_CHUNK", 251)
    monkeypatch.setattr(C, "_THREADS", 1)
    one = C.follow_steps(loss_fn, params, shards, masks, **kw)
    monkeypatch.setattr(C, "_THREADS", 32)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        many = C.follow_steps(loss_fn, params, shards, masks, **kw)
    finally:
        sys.setswitchinterval(interval)
    assert many["losses"] == one["losses"]
    for key in ("first_grad", "params"):
        np.testing.assert_array_equal(many[key], one[key], key)
    for a, b in zip(many["first_grad_workers"], one["first_grad_workers"]):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------ a loss without the tiny VGG

WIDTHS = (96, 1024, 1024, 10)           # 1.16 M parameters


def mlp_params(seed=3):
    rng = np.random.default_rng(seed)
    out = {}
    for i, (a, b) in enumerate(zip(WIDTHS, WIDTHS[1:])):
        out[f"Dense_{i}/kernel"] = (rng.standard_normal((a, b))
                                    / np.sqrt(a)).astype(np.float32)
        out[f"Dense_{i}/bias"] = np.zeros((b,), np.float32)
    return out


def mlp_loss(params, batch):
    """A plain MLP: one full-length vector of it shows among live arrays."""
    x, y = batch
    for i in range(len(WIDTHS) - 1):
        x = C.dense(x, params[f"Dense_{i}/kernel"], params[f"Dense_{i}/bias"])
        if i < len(WIDTHS) - 2:
            x = jax.nn.relu(x)
    return C.cross_entropy(x, y)


def mlp_shards(steps, workers, rows=24, seed=9):
    rng = np.random.default_rng(seed)
    return [[(rng.standard_normal((rows, WIDTHS[0])).astype(np.float32),
              rng.integers(0, 10, (rows,)).astype(np.int32))
             for _ in range(workers)] for _ in range(steps)]


class LiveArrays:
    """A probe that reads, as each gradient call returns and as each step
    ends, the bytes of every array alive on a device, less those that were
    alive before, in full-length float32 vectors."""

    def __init__(self, n):
        self.n = n
        self.before = self.alive()
        self.seen = {"grad_returned": [], "step_end": []}

    @staticmethod
    def alive():
        return sum(a.nbytes for a in jax.live_arrays())

    def __call__(self, event, info=None):
        if event in self.seen:
            self.seen[event].append(
                (self.alive() - self.before) / (4.0 * self.n))


def test_no_more_than_five_full_length_arrays_are_alive_on_the_device():
    """Four workers, sparse: the old implementation held p, m, four
    residuals, the tree, G, gsum, a gradient and the first step's four
    gradients there, and twelve masks of a byte an entry. The new one
    holds the tree and one gradient."""
    params, shards = mlp_params(), mlp_shards(3, 4)
    n = sum(v.size for v in params.values())
    rng = np.random.default_rng(1)
    masks = [[rng.random(n) < 0.01 for _ in range(4)] for _ in range(3)]
    kw = dict(lrs=[0.1] * 3, momentum=0.9, weight_decay=1e-4)
    probe = LiveArrays(n)
    C.follow_steps(mlp_loss, params, shards, masks, probe=probe, **kw)
    assert len(probe.seen["grad_returned"]) == 3 * 4
    assert len(probe.seen["step_end"]) == 3
    most = max(probe.seen["grad_returned"])
    assert 1.9 < most <= 2.1, probe.seen
    # as a step ends nothing full-length is left there at all
    assert max(probe.seen["step_end"]) < 0.1, probe.seen
    old = LiveArrays(n)
    follow_steps_before_pr26(
        mlp_loss, {k: jnp.asarray(v) for k, v in params.items()},
        [[tuple(jnp.asarray(a) for a in b) for b in row] for row in shards],
        [[jnp.asarray(x) for x in row] for row in masks], probe=old, **kw)
    assert max(old.seen["grad_returned"]) > 11, old.seen


def test_the_checks_memory_probe_counts_the_gradient_program():
    from benchmarks import check
    probe = check.MemoryProbe()
    C.follow_steps(mlp_loss, mlp_params(), mlp_shards(1, 1), [None],
                   lrs=[0.1], momentum=0.9, weight_decay=0.0, probe=probe)
    got = probe.report()
    assert got["peak_bytes"] >= got["arrays_peak_bytes"] >= 0
    assert got["grad_call_temp_bytes"] >= 0
    assert set(got) == {"at_start_bytes", "arrays_peak_bytes",
                        "grad_call_temp_bytes",
                        "grad_call_fresh_output_bytes", "peak_bytes"}


# ------------------------------------------------ long arrays to the host

@pytest.mark.parametrize("shape,spec", [
    ((3, 5, 7, 11), None),                      # a leaf, one device
    ((4 * 1000,), "dp"),                        # a residual, a row a device
    ((4096,), ()),                              # a momentum on four devices
])
def test_fetch_reads_what_asarray_reads(shape, spec, monkeypatch):
    """In slices (here of 100 elements, the last one short) on threads,
    each distinct shard once, into a buffer of the caller's or a new
    one."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    monkeypatch.setattr(C, "_SLICE", 100)
    rng = np.random.default_rng(8)
    host = rng.standard_normal(shape).astype(np.float32)
    if spec is None:
        x = jnp.asarray(host)
    else:
        mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
        x = jax.device_put(host, NamedSharding(mesh, P(*([spec] if spec
                                                         else []))))
        assert len(x.addressable_shards) == 4
    got = C.fetch(x)
    assert got.dtype == np.float32 and got.shape == host.shape
    np.testing.assert_array_equal(got, host)
    mine = np.full(shape, 7.0, np.float32)
    assert C.fetch(x, mine) is mine
    np.testing.assert_array_equal(mine, host)
    mask = jnp.asarray(host > 0)
    np.testing.assert_array_equal(C.fetch(mask), host > 0)
    # a short array crosses whole
    monkeypatch.setattr(C, "_SLICE", 1 << 25)
    np.testing.assert_array_equal(C.fetch(x), host)
