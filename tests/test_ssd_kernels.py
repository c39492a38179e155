"""Mamba-2's scan as Pallas kernels (`ops/ssd_scan.py`, PR 49) under the
interpreter on the CPU: against the recurrence token by token in float32
(output, final state, every cotangent, the leaves `A_log` and `dt_bias` by
name), near the chunked scan with bfloat16 products, and the mixer's choice
between the two forms by shape and backend. What Mosaic makes of them is
`tests/test_trainstep.py`'s (compiled for a described v5e) and the chip's
(`nemotronh_ssd_dp1`'s `correct`)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from gaussiank_sgd_tpu.models.blocks import ssm
from gaussiank_sgd_tpu.ops import ssd_scan

LEAVES = ("x", "dt", "A_log", "dt_bias", "B", "C")


def _inputs(low: float, key=1, b=1, t=256, g=1, r=2, p=64, n=128):
    """x, the steps before their softplus, `A_log`, `dt_bias`, B and C, with
    `dt a` uniform on about (low, 0) a token."""
    ks = jax.random.split(jax.random.PRNGKey(key), 6)
    h = g * r
    return (jax.random.normal(ks[0], (b, t, h, p)),
            jax.random.normal(ks[1], (b, t, h)),
            jnp.log(-low * jax.random.uniform(ks[2], (h,), minval=0.5,
                                              maxval=1.0)),
            jax.random.normal(ks[3], (h,)) - 1.0,
            jax.random.normal(ks[4], (b, t, g, n)) * n ** -0.5,
            jax.random.normal(ks[5], (b, t, g, n)))


def _kernels(x, dt, a, b_in, c_in):
    """The kernels on `recurrent_scan`'s arguments: `[x | B | C]` side by
    side along the last axis, as the kernels take them."""
    b, t, _, _ = x.shape
    y, state = ssd_scan.ssd_scan(
        jnp.concatenate([v.reshape(b, t, -1) for v in (x, b_in, c_in)], -1),
        dt, a, b_in.shape[2], b_in.shape[3], True)
    return y.reshape(x.shape), state


def _through(scan):
    """`scan` behind the mixer's own `dt = softplus(. + dt_bias)` and `a =
    -exp(A_log)`: the leaves only the state's path reaches."""
    def f(x, steps, a_log, dt_bias, b_in, c_in):
        return scan(x, jax.nn.softplus(steps + dt_bias), -jnp.exp(a_log),
                    b_in, c_in)
    return f


def _gradients(scan, args):
    """The cotangents of all six under a loss that reads every output and
    the final state."""
    def loss(*a):
        y, state = _through(scan)(*a)
        y = y.astype(jnp.float32)
        return (jnp.sum(y * jnp.cos(jnp.arange(y.size)).reshape(y.shape))
                + jnp.sum(state * jnp.sin(jnp.arange(state.size)).reshape(
                    state.shape)))
    return jax.grad(loss, argnums=tuple(range(6)))(*args)


@pytest.mark.parametrize("g,r", [(1, 2), (2, 4)],
                         ids=["two_heads_one_tile", "two_groups_of_four"])
@pytest.mark.parametrize("low", [-1e-3, -40.0],
                         ids=["decay_near_1", "decay_near_0"])
@pytest.mark.parametrize("most", [8, 1], ids=["one_block", "two_blocks"])
def test_the_kernels_are_the_token_by_token_recurrence(monkeypatch, g, r,
                                                       low, most):
    """Two chunks as one grid step and as two (the states and their
    cotangent cross from a step's scratch to the next), a group of one
    128-lane tile and groups of two (a head of 64 is half a tile: `M x` of
    two heads side by side), decays near 1 and near 0 (`exp(G)` underflows
    under a chunk's running sum, which passes a thousand: there the decay's
    own cotangent, differences of float32 sums, is held to 2 %, as
    `chunked_scan`'s is in `tests/test_nemotron_h.py`, and what reaches the
    steps through it to a thousandth)."""
    monkeypatch.setattr(ssd_scan, "_MOST_CHUNKS", most)
    args = _inputs(low, g=g, r=r)
    assert ssd_scan.takes(args[0].shape, args[4].shape)
    want = _through(ssm.recurrent_scan)(*args)
    got = _through(_kernels)(*args)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b),
            atol=2e-5 * float(jnp.max(jnp.abs(b))))
    for name, a, b in zip(LEAVES, _gradients(_kernels, args),
                          _gradients(ssm.recurrent_scan, args)):
        assert np.isfinite(np.asarray(a)).all(), name
        tol = 2e-4 if low > -10 else {"A_log": 0.02, "dt": 1e-3,
                                      "dt_bias": 1e-3}.get(name, 2e-4)
        assert float(jnp.max(jnp.abs(a - b))) <= tol * float(
            jnp.max(jnp.abs(b))), name


def test_a_head_of_whole_tiles():
    """A head of 128 columns is a tile of its own: no lane of `M x` is
    zeroed."""
    args = _inputs(-3.0, t=128, g=2, r=1, p=128)
    for a, b in zip(_through(_kernels)(*args),
                    _through(ssm.recurrent_scan)(*args)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b),
            atol=2e-5 * float(jnp.max(jnp.abs(b))))
    for name, a, b in zip(LEAVES, _gradients(_kernels, args),
                          _gradients(ssm.recurrent_scan, args)):
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-4 * float(
            jnp.max(jnp.abs(b))), name


def test_the_kernels_with_bfloat16_products_stay_near_the_chunked_scan():
    """Operands bfloat16, sums, decays and states float32: as far from the
    float32 recurrence as `chunked_scan(dtype=bfloat16)` is, output, final
    state and every cotangent."""
    exact = _inputs(-3.0, r=4)
    rounded = tuple(v.astype(jnp.bfloat16) if i in (0, 4, 5) else v
                    for i, v in enumerate(exact))
    y_want, s_want = _through(ssm.recurrent_scan)(*exact)
    y_got, s_got = _through(_kernels)(*rounded)
    assert y_got.dtype == jnp.bfloat16 and s_got.dtype == jnp.float32
    for got, want in ((y_got, y_want), (s_got, s_want)):
        assert float(jnp.max(jnp.abs(got - want))) < 0.03 * float(
            jnp.max(jnp.abs(want)))
    want = _gradients(ssm.recurrent_scan, exact)
    chunked = _gradients(
        lambda *a: ssm.chunked_scan(*a, dtype=jnp.bfloat16), rounded)
    for name, a, c, b in zip(LEAVES, _gradients(_kernels, rounded), chunked,
                             want):
        scale = float(jnp.max(jnp.abs(b)))
        mine = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))) / scale
        theirs = float(jnp.max(jnp.abs(c.astype(jnp.float32) - b))) / scale
        assert mine < max(0.03, 1.5 * theirs), (name, mine, theirs)


@pytest.mark.parametrize("chunks,step", [(64, 8), (20, 5), (7, 7), (34, 2),
                                         (1, 1)])
def test_a_grid_steps_chunks_divide_the_sequence(chunks, step):
    assert ssd_scan.chunks_a_step(chunks) == step


@pytest.mark.parametrize("t,h,p,g,n,taken", [
    (8192, 64, 64, 8, 128, True), (128, 2, 64, 1, 128, True),
    (256, 2, 128, 2, 256, True), (8192, 64, 64, 8, 64, False),
    (8192, 8, 64, 8, 128, False), (8192, 24, 48, 3, 128, False),
    (100, 2, 64, 1, 128, False), (128, 3, 128, 2, 128, False),
    (128, 1, 128, 1, 256, False)],
    ids=["the_cell", "one_chunk_one_tile", "heads_of_a_tile", "state_of_64",
         "a_group_of_half_a_tile", "heads_of_48", "no_whole_chunks",
         "heads_do_not_divide", "b_starts_inside_a_block"])
def test_which_shapes_the_kernels_take(t, h, p, g, n, taken):
    assert ssd_scan.takes((2, t, h, p), (2, t, g, n)) == taken


def test_the_cells_blocks_fit_the_vmem_the_calls_ask_for():
    """`nemotronh_ssd_dp1`'s shape, eight chunks a grid step: what each call
    asks for (`vmem_limit_bytes`) stands under half a core's 128 MiB;
    Mosaic's own verdict is `tests/test_trainstep.py`'s."""
    assert ssd_scan.chunks_a_step(8192 // ssd_scan.CHUNK) == 8
    for backward in (False, True):
        assert ssd_scan.vmem_bytes(8, 8, 64, 128, 2, backward) < 64 * 2 ** 20


def _mixer(kernels, heads, width, state, positions, chunk=ssm.CHUNK):
    """`Mamba2Mixer` with one group of `heads` heads of `width`, its seeded
    parameters and an input."""
    def of(kernels):
        return ssm.Mamba2Mixer(heads, width, state, 1, 4, 1e-5, jnp.float32,
                               chunk, kernels=kernels)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, positions, 32))
    return of(kernels), of(False).init(jax.random.PRNGKey(4), x), x


def _lowered(f, *args, **how):
    return jax.jit(f).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text(**how)


@pytest.mark.parametrize("heads,width,state,positions,chunk,taken", [
    (2, 64, 128, 128, 128, True), (2, 32, 16, 128, 128, False),
    (2, 64, 128, 40, 128, False), (2, 64, 128, 64, 64, False)],
    ids=["taken", "a_group_of_half_a_tile", "no_whole_chunks",
         "chunks_of_64"])
def test_the_mixer_takes_the_kernels_by_shape(heads, width, state, positions,
                                              chunk, taken):
    """With `kernels`, a shape the kernels take runs them (the lowered
    mixer holds a Mosaic call; its numbers are the chunked scan's within
    float32) and any other shape, or another chunk than the kernels' 128,
    falls back to `chunked_scan` and gives its numbers bit for bit."""
    mixer, params, x = _mixer(True, heads, width, state, positions, chunk)
    assert ("tpu_custom_call" in _lowered(mixer.apply, params, x)) == taken
    plain, _, _ = _mixer(False, heads, width, state, positions, chunk)

    def loss(m):
        def f(params, x):
            out, counters = m.apply(params, x)
            return jnp.sum(out * out) + counters["ssm_state_rms"]
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(params, x)
    want = loss(plain)
    with pltpu.force_tpu_interpret_mode():
        got = loss(mixer)
    largest = max(float(jnp.max(jnp.abs(b)))
                  for b in jax.tree_util.tree_leaves(want))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        if taken:
            scale = float(jnp.max(jnp.abs(b)))
            assert float(jnp.max(jnp.abs(a - b))) <= (2e-4 * scale
                                                      + 1e-6 * largest)
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_mixer_takes_the_kernels_by_backend():
    """`kernels=None` asks the process's backend: on the CPU the mixer at
    the kernels' own shape lowers no Mosaic call, even for the TPU, and IS
    the mixer with `kernels=False`."""
    mixer, params, x = _mixer(None, 2, 64, 128, 128)
    assert jax.default_backend() == "cpu"
    assert "tpu_custom_call" not in _lowered(mixer.apply, params, x)
    plain, _, _ = _mixer(False, 2, 64, 128, 128)
    for a, b in zip(jax.tree_util.tree_leaves(mixer.apply(params, x)),
                    jax.tree_util.tree_leaves(plain.apply(params, x))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _kernel_paths(text):
    """{kernel: its `op_name` path} of the Mosaic calls of a lowered text
    with its locations."""
    return {m.group(2): m.group(1) for m in re.finditer(
        r'"([^"]*/(ssd_\w+))/pallas_call"', text)}


def test_the_mixers_passes_are_the_three_calls_under_their_scope():
    """Lowered for the TPU, the mixer's forward pass is ONE call, `ssd_fwd`,
    which writes no states; differentiated it is `ssd_fwd_kept` and
    `ssd_bwd`; and each call's `op_name` holds `ssm/ssm_scan`, by which
    `benchmarks/scope_tree.py` books its time under `ssm_scan_ms`."""
    mixer, params, x = _mixer(True, 2, 64, 128, 256)
    forward = _lowered(lambda p, x: mixer.apply(p, x)[0], params, x,
                       debug_info=True)
    assert forward.count("tpu_custom_call") == 1
    assert set(_kernel_paths(forward)) == {"ssd_fwd"}
    both = _lowered(jax.grad(lambda p, x: jnp.sum(mixer.apply(p, x)[0])),
                    params, x, debug_info=True)
    assert both.count("tpu_custom_call") == 2
    paths = _kernel_paths(both)
    assert set(paths) == {"ssd_fwd_kept", "ssd_bwd"}
    for name, path in {**_kernel_paths(forward), **paths}.items():
        assert "ssm/ssm_scan" in path, (name, path)
