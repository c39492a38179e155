"""The mixer's prologue as Pallas kernels (`ops/delta_prologue.py`, PR 47)
under the interpreter on the CPU, held to what `models/blocks/delta.py`
states: `conv_silu` followed by `l2_normed` and the scale, forward and
through `jax.vjp`. What Mosaic makes of them is `tests/test_trainstep.py`'s
(compiled for a described v5e) and the chip's (`qwen3next_gdn_dp1`'s
`correct`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gaussiank_sgd_tpu.models.blocks import delta
from gaussiank_sgd_tpu.ops import delta_prologue

from test_delta_kernels import _mixer

DK = 128
TAPS = 4


def _xla_form(qkvz, taps, keys, dk=DK):
    """q, k [B, S, keys] and v as the mixer's XLA path makes them."""
    b, s, _ = qkvz.shape
    qkv = delta.conv_silu(qkvz[..., :taps.shape[0]], taps)

    def normed(x, scale):
        x = delta.l2_normed(x.reshape(b, s, -1, dk)) * scale
        return x.astype(qkvz.dtype).reshape(b, s, keys)
    return (normed(qkv[..., :keys], dk ** -0.5),
            normed(qkv[..., keys:2 * keys], 1.0), qkv[..., 2 * keys:])


def _kernels(qkvz, taps, keys, dk=DK):
    return delta_prologue.conv_norm(qkvz, taps, keys, dk, True)


def _inputs(b, s, hk, hv, dtype, seed=0, dk=DK):
    """`qkvz` [B, S, 2 keys + 2 values] at the cell's widths cut down in
    heads, taps large enough that every shift counts, three cotangents."""
    keys, values = hk * dk, hv * DK
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    qkvz = (1.5 * jax.random.normal(
        ks[0], (b, s, 2 * keys + 2 * values))).astype(dtype)
    taps = 0.5 * jax.random.normal(ks[1], (2 * keys + values, TAPS))
    cotangents = tuple(
        jax.random.normal(k, (b, s, n)).astype(dtype)
        for k, n in zip(ks[2:], (keys, keys, values)))
    return qkvz, taps, keys, cotangents


def _ulp(want):
    """A rounding of `want`'s dtype at each entry's size."""
    want = np.abs(np.asarray(want, np.float32))
    return np.maximum(want, 1e-30) * float(jnp.finfo(jnp.bfloat16).eps)


def _gradients(f, qkvz, taps, keys, cotangents):
    return jax.vjp(lambda x, t: f(x, t, keys), qkvz, taps)[1](cotangents)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("hk,hv", [(2, 4), (1, 1)],
                         ids=["two_to_a_key_head", "one_to_one"])
def test_the_forward_kernel_is_the_convolution_and_the_norms(dtype, hk, hv):
    """v is the XLA form's to one rounding of bfloat16 at most, q and k,
    which are rounded twice, to one at each place (the kernel's sigmoid is
    a `tanh`, its sums run in another order), and nearly every entry is the
    same bits."""
    qkvz, taps, keys, _ = _inputs(2, 64, hk, hv, dtype)
    want = _xla_form(qkvz, taps, keys)
    got = _kernels(qkvz, taps, keys)
    for name, a, b in zip("qkv", got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert (np.abs(a - b) <= (1 if name == "v" else 2) * _ulp(b)).all(), \
            name
        if dtype == jnp.bfloat16:
            assert (a != b).mean() < 0.01, name


@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, 8e-3),
                                       (jnp.float32, 2e-5)],
                         ids=["bfloat16", "float32"])
def test_the_backward_kernel_is_the_xla_forms_vjp(dtype, tol):
    """dx (the cotangents of q and k taken back through the norm, all three
    through the SiLU and the taps) and the taps' gradient against `jax.vjp`
    of the XLA form, which rounds the norm's cotangent to `dtype` on its way
    to the convolution where the kernel keeps float32. The columns of `z`
    get zeros."""
    qkvz, taps, keys, cotangents = _inputs(2, 64, 2, 4, dtype)
    want = _gradients(_xla_form, qkvz, taps, keys, cotangents)
    got = _gradients(_kernels, qkvz, taps, keys, cotangents)
    for name, a, b in zip(("dx", "d_taps"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(a).all(), name
        assert np.abs(a - b).max() <= tol * np.abs(b).max(), name
    assert not np.asarray(got[0][..., taps.shape[0]:], np.float32).any()


def test_a_key_head_of_two_lane_blocks_is_normed_whole():
    """Key heads of 256 (two 128-lane blocks to a norm) beside value
    columns taken 128 at a time: outputs and both gradients as at 128."""
    qkvz, taps, keys, cotangents = _inputs(1, 32, 2, 3, jnp.float32, dk=256)

    def both(f):
        def g(x, t, keys):
            return f(x, t, keys, 256)
        return g(qkvz, taps, keys) + _gradients(g, qkvz, taps, keys,
                                               cotangents)
    for a, b in zip(both(_kernels), both(_xla_form)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-5 * float(jnp.max(jnp.abs(b))))


@pytest.mark.parametrize("most", [48, 32, 16],
                         ids=["two_blocks", "three_blocks", "six_blocks"])
def test_blocks_of_positions_are_one_block(monkeypatch, most):
    """A sequence as two, three and six grid steps against one: the halo
    before a block (the one-tile view; zeros before the first) and after it
    (the sum's cotangent left by the step before; nothing after the last)
    give the same q, k, v and dx bit for bit; the taps' gradient is summed
    in another order."""
    qkvz, taps, keys, cotangents = _inputs(1, 96, 1, 2, jnp.bfloat16)
    assert delta_prologue.rows_a_step(96) == 96
    whole = (_kernels(qkvz, taps, keys),
             _gradients(_kernels, qkvz, taps, keys, cotangents))
    monkeypatch.setattr(delta_prologue, "_MOST_ROWS", most)
    assert delta_prologue.rows_a_step(96) == most
    cut = (_kernels(qkvz, taps, keys),
           _gradients(_kernels, qkvz, taps, keys, cotangents))
    for a, b in zip(cut[0] + cut[1][:1], whole[0] + whole[1][:1]):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    np.testing.assert_allclose(np.asarray(cut[1][1]),
                               np.asarray(whole[1][1]), rtol=1e-5, atol=1e-5)


def test_nothing_crosses_from_a_sequence_into_the_next(monkeypatch):
    """Two sequences in a batch, two blocks each: the second's first
    positions see zeros and not the first's last ones, the first's last
    positions get no cotangent from the second's first ones; each is what
    it is alone, and the taps' gradient is the two's sum."""
    monkeypatch.setattr(delta_prologue, "_MOST_ROWS", 16)
    qkvz, taps, keys, cotangents = _inputs(2, 32, 1, 2, jnp.bfloat16)
    q, k, v = _kernels(qkvz, taps, keys)
    dx, d_taps = _gradients(_kernels, qkvz, taps, keys, cotangents)
    alone = 0.0
    for i in range(2):
        one = slice(i, i + 1)
        outs = _kernels(qkvz[one], taps, keys)
        dx_i, d_taps_i = _gradients(_kernels, qkvz[one], taps, keys,
                                    tuple(c[one] for c in cotangents))
        for a, b in zip((q, k, v, dx), outs + (dx_i,)):
            np.testing.assert_array_equal(np.asarray(a[one], np.float32),
                                          np.asarray(b, np.float32))
        alone = alone + d_taps_i
    np.testing.assert_allclose(np.asarray(d_taps), np.asarray(alone),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("positions,dk,dv,length,taken", [
    (8192, 128, 128, 4, True), (64, 256, 128, 2, True),
    (16, 128, 128, 9, True), (8192, 64, 128, 4, False),
    (8192, 128, 192, 4, False), (100, 128, 128, 4, False),
    (8192, 128, 128, 10, False)],
    ids=["the_cell", "a_key_head_of_256", "one_tile", "key_head_of_64",
         "value_head_of_192", "no_whole_tiles", "taps_past_a_vreg"])
def test_which_shapes_the_prologue_takes(positions, dk, dv, length, taken):
    assert delta_prologue.takes(positions, dk, dv, length) == taken


@pytest.mark.parametrize("positions,rows", [(8192, 256), (96, 96), (320, 160),
                                            (48, 48), (24, 0)])
def test_a_grid_steps_rows_divide_the_sequence(positions, rows):
    assert delta_prologue.rows_a_step(positions) == rows


@pytest.mark.parametrize("width,positions,taken", [
    (128, 128, True), (64, 128, False), (128, 100, False)],
    ids=["taken", "a_head_of_64", "no_whole_chunks"])
def test_the_mixer_takes_the_prologue_by_shape(width, positions, taken):
    """With `kernels`, a shape that the rule's kernels and the prologue's
    both take runs both (the lowered mixer names them under its scopes); any
    other shape lowers to no kernel at all, `conv_silu` and `l2_normed` as
    on the CPU."""
    mixer, params, x = _mixer(True, width, positions)
    text = jax.jit(mixer.apply).trace(params, x).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert ("gdn_conv/gdn_conv_fwd" in text) == taken
    assert ("gdn_rule/gdn_fwd" in text) == taken
    assert ("tpu_custom_call" in text) == taken
