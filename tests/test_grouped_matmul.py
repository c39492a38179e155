"""`ops/grouped_matmul.py` (PR 41): the experts' three grouped products as
Pallas kernels on tiles computed from the shape, here under the Pallas
interpreter, against `lax.ragged_dot` and against a plain product a group
at a time in float32; `models/blocks/experts.grouped_product` and the expert
layer with the kernels against the same without; the tile chooser at the
four transformer cells' shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.experimental.pallas import tpu as pltpu

from gaussiank_sgd_tpu.models.blocks import experts as moe
from gaussiank_sgd_tpu.ops import grouped_matmul as gm

# name: (rows of room, contraction, width, the groups' rows, tiles or None
# for the chooser's)
CASES = {
    # 256 rows a tile: the last 256 rows are visited by no group
    "rows_past_the_last_group": (768, 256, 128, (100, 60, 90, 6), None),
    "an_empty_group_first_inside_and_last": (
        512, 128, 256, (0, 130, 0, 126, 0, 200, 0), None),
    "a_group_ends_inside_a_row_tile": (512, 128, 128, (100, 300, 112), None),
    "a_tile_of_three_groups": (256, 128, 128, (40, 50, 60, 106), None),
    # the cells' 2304 x 896 and 2048 x 768 by an eighth: neither the
    # contraction nor the width is a multiple of its tile
    "mellum2_by_an_eighth_tiles_of_128": (
        512, 288, 112, (100, 0, 130, 60, 180), (128, 128, 128)),
    "mellum2_by_an_eighth_whole": (
        512, 288, 112, (100, 0, 130, 60, 180), None),
    "joyai_by_an_eighth_tiles_of_128": (
        256, 256, 96, (31, 97, 5, 64), (128, 128, 128)),
    "joyai_by_an_eighth_contraction_in_three": (
        256, 96, 256, (31, 97, 5, 64), (256, 128, 128)),
}


def _operands(case):
    m, k, n, sizes, tiling = CASES[case]
    rng = np.random.default_rng(sum(sizes))
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.bfloat16)
    g = jnp.asarray(rng.normal(size=(m, n)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(len(sizes), k, n)), jnp.bfloat16)
    return x, g, w, jnp.asarray(sizes, jnp.int32), tiling


def _plain(x, w, sizes, transposed=False):
    """`x[rows of e] @ w[e]` a group at a time, float32, zeros elsewhere."""
    x, w = np.asarray(x, np.float32), np.asarray(w, np.float32)
    out = np.zeros((x.shape[0], w.shape[1 if transposed else 2]), np.float32)
    start = 0
    for e, size in enumerate(np.asarray(sizes)):
        out[start:start + size] = x[start:start + size] @ (
            w[e].T if transposed else w[e])
        start += size
    return out


def _plain_by_group(x, g, sizes):
    x, g = np.asarray(x, np.float32), np.asarray(g, np.float32)
    ends = np.cumsum(np.asarray(sizes))
    return np.stack([x[end - size:end].T @ g[end - size:end]
                     for end, size in zip(ends, np.asarray(sizes))])


@pytest.mark.parametrize("which", ["forward", "rows_cotangent",
                                   "weights_cotangent"])
@pytest.mark.parametrize("case", list(CASES))
def test_a_product_against_ragged_dot_and_the_plain_formula(case, which):
    x, g, w, sizes, tiling = _operands(case)
    live = int(sizes.sum())
    if which == "forward":
        got = gm.grouped(x, w, sizes, tiling=tiling, interpret=True)
        same = lax.ragged_dot(x, w, sizes)
        want = _plain(x, w, sizes)
    elif which == "rows_cotangent":
        # contraction and width change places: so do their tiles
        got = gm.grouped(g, w, sizes, transposed=True, interpret=True,
                         tiling=tiling and (tiling[0], tiling[2], tiling[1]))
        same = lax.ragged_dot(g, jnp.swapaxes(w, 1, 2), sizes)
        want = _plain(g, w, sizes, transposed=True)
    else:
        got = gm.grouped_by_group(x, g, sizes, tiling=tiling, interpret=True)
        same = lax.ragged_dot_general(x, g, sizes, moe._BY_GROUP,
                                      preferred_element_type=jnp.float32)
        want = _plain_by_group(x, g, sizes)
        assert got.dtype == jnp.float32 and got.shape == w.shape
        live = got.shape[0]
    assert got.dtype == same.dtype
    got, same = (np.asarray(v, np.float32)[:live] for v in (got, same))
    # the same products in another order of accumulation, rounded once
    np.testing.assert_allclose(got, same, rtol=1e-2, atol=1e-4)
    np.testing.assert_allclose(got, want[:live], rtol=1e-2, atol=0.13)


@pytest.mark.parametrize("case", [
    "rows_past_the_last_group", "an_empty_group_first_inside_and_last",
    "mellum2_by_an_eighth_whole"])
def test_grouped_product_with_the_kernels_is_the_one_without(case):
    """Through the `custom_vjp`: value, the rows' cotangent (zero past the
    last group's rows, like the value) and the weights' in float32."""
    x, g, w, sizes, _ = _operands(case)
    w = w.astype(jnp.float32)
    live = int(sizes.sum())

    def both(kernels):
        def loss(x, w):
            y = moe.grouped_product(x, w, sizes, kernels)
            return jnp.sum((y * g).astype(jnp.float32)), y
        (_, y), (dx, dw) = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(x, w)
        return y, dx, dw

    with pltpu.force_tpu_interpret_mode():
        assert "grouped_dw" in str(jax.make_jaxpr(
            lambda x, w: jax.grad(lambda *a: jnp.sum(moe.grouped_product(
                *a, sizes, True).astype(jnp.float32)), argnums=1)(x, w))(x, w))
        got = both(True)
    want = both(False)
    assert [v.dtype for v in got] == [x.dtype, x.dtype, jnp.float32]
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-2, atol=2e-2)
    assert live < x.shape[0]
    assert not np.asarray(got[0][live:], np.float32).any()
    assert not np.asarray(got[1][live:], np.float32).any()


@pytest.mark.parametrize("forced,side", [((1,), "small"),
                                         ((0, 1, 2), "large")])
def test_the_expert_layer_with_the_kernels_on_either_side_of_its_room(
        forced, side):
    """16 experts, 4 a token, a quarter held: room for twice an even load's
    rows (128) where the forced experts' rows fit, for all 256 where not
    (`expert_terms`' large side, recomputed in the backward pass)."""
    rng = np.random.default_rng(5)
    tokens, hidden, width, experts, top, shares = 64, 128, 128, 16, 4, 4
    x = jnp.asarray(rng.normal(size=(2, tokens // 2, hidden)), jnp.float32)
    x = x.at[..., 0].set(5.0)
    router = 0.01 * rng.normal(size=(hidden, experts))
    router[0, list(forced)] = 10.0
    params = {"router": jnp.asarray(router, jnp.float32)}
    for name, shape in (("w1", (4, hidden, width)), ("w3", (4, hidden, width)),
                        ("w2", (4, width, hidden))):
        params[name] = jnp.asarray(0.1 * rng.normal(size=shape), jnp.float32)

    def run(kernels):
        layer = moe.Experts(experts, top, width, 0, shares, jnp.float32,
                                kernels=kernels)

        def loss(p):
            y, counters = layer.apply({"params": p}, x)
            return jnp.sum(y ** 2), (y, counters)
        (_, (y, counters)), grads = jax.value_and_grad(
            loss, has_aux=True)(params)
        return y, counters, grads

    with pltpu.force_tpu_interpret_mode():
        y, counters, grads = run(True)
    y0, _, grads0 = run(False)
    assert (float(counters["moe_held_assignments"]) > 128) == (side == "large")
    np.testing.assert_allclose(np.asarray(y), np.asarray(y0), atol=1e-4,
                               rtol=1e-4)
    for name in ("w1", "w3", "w2"):
        assert float(jnp.max(jnp.abs(grads0[name]))) > 1e-3
        np.testing.assert_allclose(np.asarray(grads[name]),
                                   np.asarray(grads0[name]), atol=1e-4,
                                   rtol=1e-3)


# (rows of room, hidden, width) of the four transformer cells
CELLS = {"mellum2_moe_dp1": (32768, 2304, 896),
         "lfm2_conv_dp1": (32768, 2048, 1792),
         "trinity_gated_dp1": (16384, 2048, 1024),
         "joyai_mla_dp1": (8192, 2048, 768)}


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_tiles_at_a_cells_shape(cell):
    """Multiples of (8, 128) that divide the rows, double-buffered inside
    the VMEM budget, a function of the shape and nothing else; with the
    whole matrix of an expert as one block at these widths, so it is read
    once a group."""
    m, hidden, width = CELLS[cell]
    for k, n in ((hidden, width), (width, hidden)):
        for choose, need in ((gm.tiles, gm.gmm_bytes),
                             (gm.tiles_by_group, gm.tgmm_bytes)):
            t = choose(m, k, n)
            assert t == choose(m, k, n) and len(t) == 3
            tm, tk, tn = t
            assert tm % 8 == 0 and m % tm == 0 and tm in gm.ROW_TILES
            assert tk % 128 == 0 and tn % 128 == 0
            assert need(t) <= gm.VMEM_BUDGET < 128 * 2 ** 20
            assert (tk, tn) == (k, n)
    # the large side's room (every assignment) takes the same tiles
    assert gm.tiles(4 * m, hidden, width) == gm.tiles(m, hidden, width)


@pytest.mark.parametrize("rows,contraction,width,want", [
    (32768 + 8, 2304, 896, None),       # no row tile divides the room
    (1000, 64, 64, None),
    (384, 64, 32, (128, 64, 32)),       # 128 rows where 256 do not divide
    # an expert too large for VMEM whole: the width is cut first
    (32768, 4096, 4096, (256, 4096, 2048)),
    (32768, 8192, 8192, (256, 8192, 1024))])
def test_the_tiles_of_other_shapes(rows, contraction, width, want):
    assert gm.tiles(rows, contraction, width) == want
    if want:
        assert gm.gmm_bytes(want) <= gm.VMEM_BUDGET
        by_group = gm.tiles_by_group(rows, contraction, width)
        assert gm.tgmm_bytes(by_group) <= gm.VMEM_BUDGET
