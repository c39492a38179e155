"""The gated delta rule's Pallas kernels (`ops/delta_rule.py`, PR 45; the
prologue's, PR 47, are `tests/test_delta_prologue.py`'s) under
the interpreter on the CPU: against the rule token by token in float32
(output, final state, all five cotangents), near the chunked rule with
bfloat16 products, and the mixer's choice between them by shape. What Mosaic
makes of them is `tests/test_trainstep.py`'s (compiled for a described v5e)
and the chip's (`qwen3next_gdn_dp1`'s `correct`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from gaussiank_sgd_tpu.models.blocks import delta
from gaussiank_sgd_tpu.ops import delta_rule

from test_qwen3_next import _rule_gradients, _rule_inputs

WIDE = dict(dk=128, dv=128, b=1)


def _kernels(q, k, v, g, beta):
    """The kernels on `recurrent_rule`'s arguments: heads side by side along
    the last axis, as the kernels take and give them."""
    (b, t, _, dk), (_, _, h, dv) = q.shape, v.shape
    o, state = delta_rule.gated_delta_rule(
        q.reshape(b, t, -1), k.reshape(b, t, -1), v.reshape(b, t, -1), g,
        beta, dk, True)
    return o.reshape(b, t, h, dv), state


@pytest.mark.parametrize("hk,h", [(1, 2), (2, 2)],
                         ids=["two_to_a_key_head", "one_to_one"])
@pytest.mark.parametrize("low", [-1e-3, -40.0],
                         ids=["decay_near_1", "decay_near_0"])
@pytest.mark.parametrize("most", [16, 2], ids=["one_block", "two_blocks"])
def test_the_kernels_are_the_token_by_token_rule(monkeypatch, hk, h, low,
                                                 most):
    """Four chunks as one grid step (one round of the kernels' loop) and as
    two (the state and its cotangent cross from a step's scratch to the
    next), two value heads to a key head (in one grid step, their `Q K^T`
    and `K K^T` shared, dq and dk summed) and one to one, decays near 1
    and near 0 (`exp(G)` underflows under a chunk's running sum)."""
    monkeypatch.setattr(delta_rule, "_MOST_CHUNKS", most)
    args = _rule_inputs(low, t=256, hk=hk, h=h, **WIDE)
    assert delta_rule.takes(args[0].shape, args[2].shape)
    o_want, s_want = delta.recurrent_rule(*args)
    o_got, s_got = _kernels(*args)
    assert o_got.shape == o_want.shape and s_got.shape == s_want.shape
    np.testing.assert_allclose(np.asarray(o_got), np.asarray(o_want),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(s_got), np.asarray(s_want),
                               atol=2e-5)
    want = _rule_gradients(delta.recurrent_rule, args)
    got = _rule_gradients(_kernels, args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert np.isfinite(np.asarray(a)).all(), name
        scale = float(jnp.max(jnp.abs(b)))
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-4 * scale, name


@pytest.mark.parametrize("chunks", [3, 8], ids=["three", "eight"])
def test_a_round_of_the_kernels_loop_packs_its_chunks(chunks):
    """A round's chunks are solved together: sixteen diagonal blocks (four
    chunks') to a substitution, two chunks to a join. Three chunks are a
    group short of four and a pair with a single; eight are a round of the
    size the cell runs, two groups and four pairs."""
    args = _rule_inputs(-3.0, t=64 * chunks, hk=1, h=1, **WIDE)
    assert delta_rule.chunks_a_step(chunks) == chunks <= delta_rule._ROUND
    want = delta.recurrent_rule(*args)
    got = _kernels(*args)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
    for name, a, b in zip("q k v g beta".split(),
                          _rule_gradients(_kernels, args),
                          _rule_gradients(delta.recurrent_rule, args)):
        scale = float(jnp.max(jnp.abs(b)))
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-4 * scale, name


def test_the_kernels_with_bfloat16_products_stay_near_the_chunked_rule():
    """Operands bfloat16, sums, state and inverse float32: as far from the
    float32 rule as `chunked_rule(dtype=bfloat16)` is, output and every
    cotangent."""
    q, k, v, g, beta = _rule_inputs(-3.0, t=128, hk=1, h=2, **WIDE)
    exact = (q, k, v, g, beta)
    rounded = tuple(a.astype(jnp.bfloat16) for a in (q, k, v)) + (g, beta)
    o_want, s_want = delta.recurrent_rule(*exact)
    o_got, s_got = _kernels(*rounded)
    assert o_got.dtype == jnp.bfloat16 and s_got.dtype == jnp.float32
    for got, want in ((o_got, o_want), (s_got, s_want)):
        assert float(jnp.max(jnp.abs(got - want))) < 0.03 * float(
            jnp.max(jnp.abs(want)))
    want = _rule_gradients(delta.recurrent_rule, exact)
    chunked = _rule_gradients(
        lambda *a: delta.chunked_rule(*a, dtype=jnp.bfloat16), rounded)
    got = _rule_gradients(_kernels, rounded)
    for name, a, c, b in zip("q k v g beta".split(), got, chunked, want):
        scale = float(jnp.max(jnp.abs(b)))
        mine = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))) / scale
        theirs = float(jnp.max(jnp.abs(c.astype(jnp.float32) - b))) / scale
        assert mine < max(0.03, 1.5 * theirs), (name, mine, theirs)


@pytest.mark.parametrize("chunks,step", [(128, 16), (20, 10), (7, 7),
                                         (34, 2), (1, 1)])
def test_a_grid_steps_chunks_divide_the_sequence(chunks, step):
    assert delta_rule.chunks_a_step(chunks) == step


@pytest.mark.parametrize("t,hk,h,dk,dv,taken", [
    (8192, 16, 32, 128, 128, True), (64, 1, 1, 128, 256, True),
    (8192, 16, 32, 64, 128, False), (8192, 16, 32, 128, 64, False),
    (100, 1, 2, 128, 128, False), (128, 2, 3, 128, 128, False)],
    ids=["the_cell", "one_chunk", "key_head_of_64", "value_head_of_64",
         "no_whole_chunks", "heads_do_not_divide"])
def test_which_shapes_the_kernels_take(t, hk, h, dk, dv, taken):
    assert delta_rule.takes((2, t, hk, dk), (2, t, h, dv)) == taken


def _mixer(kernels, width, positions):
    """`GatedDeltaNet` with one key head and two value heads of `width`,
    its seeded parameters and an input."""
    mixer = delta.GatedDeltaNet(1, 2, width, width, 4, 1e-6, jnp.float32,
                                kernels=kernels)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, positions, 32))
    params = delta.GatedDeltaNet(
        1, 2, width, width, 4, 1e-6, jnp.float32, kernels=False).init(
            jax.random.PRNGKey(4), x)
    return mixer, params, x


@pytest.mark.parametrize("width,positions,taken", [
    (128, 128, True), (64, 128, False), (128, 100, False)],
    ids=["taken", "a_head_of_64", "no_whole_chunks"])
def test_the_mixer_takes_the_kernels_by_shape(width, positions, taken):
    """With `kernels`, a shape the kernels take runs them (the lowered
    mixer holds a Mosaic call; its numbers are the chunked rule's within
    float32) and any other shape falls back to `chunked_rule` and gives its
    numbers exactly."""
    mixer, params, x = _mixer(True, width, positions)
    text = jax.jit(mixer.apply).trace(params, x).lower(
        lowering_platforms=("tpu",)).as_text()
    assert ("tpu_custom_call" in text) == taken
    plain, _, _ = _mixer(False, width, positions)

    def loss(m):
        def f(params, x):
            out, counters = m.apply(params, x)
            return jnp.sum(out * out) + counters["gdn_state_rms"]
        return jax.value_and_grad(f, argnums=(0, 1))(params, x)
    want = loss(plain)
    with pltpu.force_tpu_interpret_mode():
        got = loss(mixer)
    # the decay's leaves' gradients lie six orders under the matrices' and
    # are sums that cancel: held to the largest leaf's scale as well
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


def test_the_mixers_backward_pass_is_two_kernel_calls():
    """Differentiated, the mixer calls `gdn_fwd_kept` and `gdn_bwd`, and
    around them the prologue's two (PR 47: `gdn_conv_fwd` before the rule,
    `gdn_conv_bwd` after its backward kernel): two calls a way, four in all;
    the forward pass alone `gdn_conv_fwd` and `gdn_fwd`, which writes no
    states."""
    mixer, params, x = _mixer(True, 128, 128)

    def lowered(f):
        return jax.jit(f).trace(params, x).lower(
            lowering_platforms=("tpu",)).as_text()
    forward = lowered(lambda p, x: mixer.apply(p, x)[0])
    assert forward.count("tpu_custom_call") == 2
    assert "gdn_fwd" in forward and "gdn_conv_fwd" in forward
    assert "gdn_fwd_kept" not in forward and "gdn_bwd" not in forward
    assert "gdn_conv_bwd" not in forward
    both = lowered(jax.grad(lambda p, x: jnp.sum(mixer.apply(p, x)[0])))
    assert both.count("tpu_custom_call") == 4
    for name in ("gdn_conv_fwd", "gdn_fwd_kept", "gdn_bwd", "gdn_conv_bwd"):
        assert name in both, name
