"""The select kernels lower for the TPU — checked without one.

``.trace(...).lower(lowering_platforms=("tpu",))`` runs the Pallas -> Mosaic
lowering on the CPU host in seconds. It is the stage that rejects an illegal
block shape ("last two dimensions of your block shape are divisible by 8 and
128"), which interpret mode never sees: before PR 21 every uniform chunk
under 65 536 elements was refused there. The contract: every geometry a
bucket plan can produce either lowers to a ``tpu_custom_call`` or is reported
ineligible by the geometry gate (``ef_padded_chunk`` / ``_require_capacity``)
BEFORE lowering — the compiler is never what says no.

Lowering is not compiling: Mosaic's own passes and the run are the chip's to
prove (chip_smoke.py).
"""

import functools
import math
import re

import jax
import jax.numpy as jnp
import pytest

from gaussiank_sgd_tpu.compressors import get_compressor
from gaussiank_sgd_tpu.ops import grouped_matmul
from gaussiank_sgd_tpu.ops.pallas_pack import (
    _chunk_geometry, ef_padded_chunk, fused_ef_select_candidates_chunked,
    fused_select_candidates_chunked, gaussian_fused_compress_batched,
    gaussian_fused_ef_compress_batched)

# parameter counts of the five shipped configs (exp_configs/): each is a
# single whole-model bucket under the default greedy plan
MODEL_NUMEL = {"resnet20": 269_722, "vgg16": 14_986_698,
               "resnet50": 25_557_032, "lstm": 19_775_200,
               "transformer": 60_524_544}
UNIFORM_CHUNKS = (8192, 65_536, 100_000, 1 << 22)
DENSITIES = (0.001, 0.01)

GRID = ([(1, n, d) for n in MODEL_NUMEL.values() for d in DENSITIES]
        + [(3, c, d) for c in UNIFORM_CHUNKS for d in DENSITIES])


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _lowers_with_kernel(fn, *avals, **kw_avals) -> bool:
    text = jax.jit(fn).trace(*avals, **kw_avals).lower(
        lowering_platforms=("tpu",)).as_text()
    return "tpu_custom_call" in text


@pytest.mark.parametrize("n_chunks,chunk,density", GRID)
def test_select_kernel_lowers_for_every_plan_geometry(n_chunks, chunk,
                                                      density):
    """The unfused form pads each chunk to its block itself, so every
    geometry must lower — raw kernel and the full select+pack wrapper."""
    k = math.ceil(density * chunk)
    assert _lowers_with_kernel(
        functools.partial(fused_select_candidates_chunked, density=density,
                          interpret=False),
        _f32(n_chunks, chunk), _f32(n_chunks))
    assert _lowers_with_kernel(
        functools.partial(gaussian_fused_compress_batched, k=k,
                          density=density, interpret=False),
        _f32(n_chunks, chunk), state=_f32(n_chunks))


@pytest.mark.parametrize("n_chunks,chunk,density", GRID)
def test_fused_ef_kernel_lowers_or_gate_says_no(n_chunks, chunk, density):
    """The fused EF+select form takes pre-padded buffers: the gate returns
    the padded chunk (a pure suffix pad for one bucket; multi-chunk plans
    are eligible only when already aligned) and what it accepts lowers."""
    k = math.ceil(density * chunk)
    cp = ef_padded_chunk(chunk, k, density=density)
    assert cp is not None and cp >= chunk      # below the density ceiling
    if n_chunks > 1 and cp != chunk:
        # trainstep._fused_ef_layout keeps the unfused accumulate here
        with pytest.raises(ValueError, match="pre-padded block-aligned"):
            jax.eval_shape(
                functools.partial(gaussian_fused_ef_compress_batched, k=k,
                                  density=density, interpret=False),
                _f32(n_chunks, chunk), _f32(n_chunks, chunk), _f32(),
                state=_f32(n_chunks))
        return
    assert _lowers_with_kernel(
        functools.partial(fused_ef_select_candidates_chunked,
                          density=density, interpret=False),
        _f32(n_chunks, cp), _f32(n_chunks, cp), _f32(), _f32(n_chunks))
    assert _lowers_with_kernel(
        functools.partial(gaussian_fused_ef_compress_batched, k=k,
                          density=density, interpret=False),
        _f32(n_chunks, cp), _f32(n_chunks, cp), _f32(),
        state=_f32(n_chunks))


# (rows of room, hidden, width) of the experts in the four transformer
# cells, and `expert_terms`' large side in the first (every assignment)
EXPERT_SHAPES = [(32768, 2304, 896), (32768, 2048, 1792), (16384, 2048, 1024),
                 (8192, 2048, 768), (131072, 2304, 896)]


@pytest.mark.parametrize("rows,hidden,width", EXPERT_SHAPES)
def test_grouped_products_lower_at_the_cells_shapes(rows, hidden, width):
    """The three kernels of `ops/grouped_matmul.py` on the tiles they
    choose, for both products of an expert (hidden to width and back),
    each call under the name of its pass."""
    sizes = jax.ShapeDtypeStruct((8,), jnp.int32)
    for k, n in ((hidden, width), (width, hidden)):
        x, g, w = (jax.ShapeDtypeStruct(s, jnp.bfloat16)
                   for s in ((rows, k), (rows, n), (8, k, n)))
        for fn, args, name in (
                (grouped_matmul.grouped, (x, w, sizes), "grouped_fwd"),
                (functools.partial(grouped_matmul.grouped, transposed=True,
                                   name="grouped_dx"), (g, w, sizes),
                 "grouped_dx"),
                (grouped_matmul.grouped_by_group, (x, g, sizes),
                 "grouped_dw")):
            text = jax.jit(fn).trace(*args).lower(
                lowering_platforms=("tpu",)).as_text()
            assert "tpu_custom_call" in text and name in text


def _loc_names(txt):
    """op line -> every quoted name on its location chain (the name stack
    with its scopes, and the functions of the call sites)."""
    table = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", txt, re.M))

    @functools.lru_cache(maxsize=None)
    def names(ref):
        body = table.get(ref, "")
        found = frozenset(re.findall(r'"([^"]*)"', body))
        for inner in re.findall(r"#loc\d+", body):
            found |= names(inner)
        return found

    # @main only: a private function (a jitted `jnp.where`) names its body
    # relative to itself, and its `call` carries the caller's scopes
    main = txt[txt.index("func.func public @main"):]
    for line in main[:main.index("\n  }")].splitlines():
        ref = re.search(r"loc\((#loc\d+)\)\s*$", line)
        if ref and ("stablehlo." in line or "chlo." in line
                    or " call @" in line):
            yield line, names(ref.group(1))


def test_selection_sorts_no_candidate_buffer_and_keeps_its_scopes():
    """ResNet-50's one bucket, the benchmark's `resnet50_dp1`: no sort or
    top-k of the lowered compression takes an nc-sized operand (the parent's
    `approx_top_k` did: one full sort of 399 360 pairs), and every operation
    of the selection is under the scope a device trace books it by —
    `cand_topk` for finding the slots, `cand_topk` or `pack` for reading
    them (`cand_topk_ms` would read nothing from a selection without it)."""
    n, density = MODEL_NUMEL["resnet50"], 0.001
    k = math.ceil(density * n)
    cp = ef_padded_chunk(n, k, density=density)
    nc = _chunk_geometry(cp, density)[3]
    assert nc == 399_360
    txt = jax.jit(functools.partial(
        gaussian_fused_ef_compress_batched, k=k, density=density,
        interpret=False)).trace(
            _f32(1, cp), _f32(1, cp), _f32(), state=_f32(1)).lower(
                lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert "tpu_custom_call" in txt
    found = {"cand_topk": 0, "pack": 0}
    for line, names in _loc_names(txt):
        if re.search(r"\bsort\b|top_k|TopK", line.split(" loc(")[0]):
            sizes = [math.prod(int(d) for d in dims.split("x") if d)
                     for dims in re.findall(r"tensor<((?:\d+x)+)", line)]
            assert max(sizes, default=0) < nc, line
        scopes = {s for name in names for s in re.split(r"[/()]", name)}
        if "_cand_top_k" in names:
            assert "cand_topk" in scopes, line
            found["cand_topk"] += 1
        elif names & {"_read_slots", "_select_candidates_topk"}:
            assert scopes & {"cand_topk", "pack"}, line
            found["pack"] += "pack" in scopes
    assert found["cand_topk"] > 30 and found["pack"] > 5, found


def test_vgg16_whole_model_bucket_is_the_smoke_geometry():
    # the figure chip_smoke.py asserts on the chip
    assert ef_padded_chunk(14_986_698, 14_987, density=0.001) == 15_073_280


def test_above_the_density_ceiling_the_gate_refuses_before_lowering():
    assert ef_padded_chunk(100_000, 6250, density=0.0625) is None
    with pytest.raises(ValueError, match="supports density"):
        jax.eval_shape(
            functools.partial(gaussian_fused_compress_batched, k=6250,
                              density=0.0625, interpret=False),
            _f32(3, 100_000), state=_f32(3))
    # ... and the registry builds (and names) the XLA selector instead
    spec = get_compressor("gaussian_fused", density=0.0625)
    assert spec.name == "gaussian_fused(warm-fallback)" and not spec.pallas
