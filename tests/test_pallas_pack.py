"""Fused Pallas select+pack kernel tests (ops/pallas_pack.py).

The north-star kernel (BASELINE.json, SURVEY.md §7 stage 6) runs here in
interpret mode on the CPU mesh; the same code path compiles via Mosaic on
TPU. Oracles are NumPy; the contract under test is pack_by_mask's
(fixed k slots, (0,0) padding, exact EF residual, magnitude truncation)
plus the kernel-specific geometry (per-column S-slot candidate cap defers
overflow to the residual, never loses it).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from gaussiank_sgd_tpu.compressors.base import pack_by_mask
from gaussiank_sgd_tpu.ops.pallas_pack import (
    _LANES, _chunk_geometry, fused_select_candidates,
    fused_select_candidates_chunked, fused_select_pack,
    gaussian_fused_compress, gaussian_fused_compress_batched,
    rows_per_block, segment_span)


def _acc(n, seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(0.0, scale, size=n), jnp.float32)


def _ef_ok(acc, res):
    acc = np.asarray(acc)
    sent = np.zeros_like(acc)
    idx = np.asarray(res.compressed.indices)
    val = np.asarray(res.compressed.values)
    np.add.at(sent, idx, val)
    np.testing.assert_allclose(sent + np.asarray(res.residual), acc,
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("n", [4096, 300_001])  # aligned and ragged sizes
def test_candidates_exact_count_and_values(n):
    acc = _acc(n)
    t = jnp.float32(2.5)
    vals, idxs, count = fused_select_candidates(acc, t, density=0.01)
    a = np.asarray(acc)
    assert int(count) == int((np.abs(a) > 2.5).sum())
    v = np.asarray(vals)
    i = np.asarray(idxs)
    valid = v != 0
    # every candidate is a real above-threshold entry with its exact value
    assert np.array_equal(v[valid], a[i[valid]])
    assert (np.abs(v[valid]) > 2.5).all()
    # no index emitted twice
    assert len(np.unique(i[valid])) == valid.sum()


def _distinct_cell_indices(n, count, density):
    """Flat indices in pairwise-DISTINCT (segment, lane) cells: consecutive
    flat indices share a row (different lanes); new segments start every
    seg*128 elements. Cell collisions are the kernel's documented one-slot
    cap — these helpers construct data where it cannot fire."""
    seg = segment_span(density)
    out = []
    base = 0
    while len(out) < count:
        assert base < n, "n too small for distinct-cell layout"
        take = min(_LANES, count - len(out))
        out.extend(range(base, base + take))
        base += seg * _LANES                   # next segment
    return np.asarray(out[:count])


def test_pack_matches_xla_magnitude_pack_without_overflow():
    # Above-threshold entries placed in pairwise-distinct cells (no
    # one-slot cap can fire): the candidate set then equals the full mask
    # and the fused pack must select the IDENTICAL set as
    # pack_by_mask("magnitude")
    n, n_hot, k = 200_000, 300, 800
    rng = np.random.default_rng(1)
    a = rng.normal(0, 0.3, n).astype(np.float32)      # background << t
    hot = _distinct_cell_indices(n, n_hot, 0.001)
    a[hot] = rng.uniform(4.0, 9.0, n_hot) * rng.choice([-1, 1], n_hot)
    acc = jnp.asarray(a)
    t = jnp.float32(3.5)
    r_fused = fused_select_pack(acc, k, t, density=0.001)
    r_ref = pack_by_mask(acc, jnp.abs(acc) > t, k, priority="magnitude")
    fi = np.asarray(r_fused.compressed.indices)
    fv = np.asarray(r_fused.compressed.values)
    ri = np.asarray(r_ref.compressed.indices)
    rv = np.asarray(r_ref.compressed.values)
    assert set(fi[fv != 0]) == set(ri[rv != 0]) == set(hot)
    assert int(r_fused.num_selected) == int(r_ref.num_selected)
    _ef_ok(acc, r_fused)


def test_truncation_drops_smallest_magnitudes():
    n, n_hot, k = 100_000, 120, 50
    rng = np.random.default_rng(2)
    a = rng.normal(0, 0.3, n).astype(np.float32)
    hot = _distinct_cell_indices(n, n_hot, 0.001)     # no cap collisions
    a[hot] = np.linspace(2.5, 8.0, n_hot) * rng.choice([-1, 1], n_hot)
    acc = jnp.asarray(a)
    t = jnp.float32(2.0)          # far more than k above threshold
    r = fused_select_pack(acc, k, t, density=0.001)
    val = np.asarray(r.compressed.values)
    assert (val != 0).sum() == k  # truncated to exactly k
    # magnitude-priority contract: the packed k are the k largest |acc|
    sent_mags = np.sort(np.abs(val))
    top_mags = np.sort(np.abs(a))[-k:]
    np.testing.assert_allclose(sent_mags, top_mags, rtol=0, atol=0)
    _ef_ok(acc, r)


def test_cell_overflow_defers_to_residual():
    # Force one (segment, lane) cell past its one-slot cap: several large
    # entries in lane 0 of the SAME segment. The kernel emits only the
    # largest per cell — the rest MUST stay in the residual.
    seg = segment_span(0.01)
    n = rows_per_block(0.01) * _LANES
    a = np.zeros(n, np.float32)
    hot = np.arange(0, seg * _LANES, _LANES)[:3]  # 3 entries, one cell
    a[hot] = 10.0 + np.arange(len(hot))           # distinct magnitudes
    acc = jnp.asarray(a)
    k = len(hot)
    r = fused_select_pack(acc, k, jnp.float32(1.0), density=0.01)
    val = np.asarray(r.compressed.values)
    idx = np.asarray(r.compressed.indices)
    valid = val != 0
    assert valid.sum() == 1                  # one-slot cap respected
    assert set(idx[valid]) == {hot[-1]}      # the largest of the cell
    # count is still the exact mask count (pre-cap observability)
    assert int(r.num_selected) == len(hot)
    _ef_ok(acc, r)                           # nothing lost


def test_warm_cold_routing_and_controller():
    acc = _acc(64_000, seed=3)
    k = 64
    # cold: unset state routes to the Gaussian estimate + bisection
    res_cold, t_cold = gaussian_fused_compress(acc, k, jnp.float32(0.0),
                                               density=0.001)
    assert float(t_cold) > 0
    count = int(jnp.sum(jnp.abs(acc) > t_cold))
    assert 0 < count <= 4 * k
    _ef_ok(acc, res_cold)
    # warm: usable state runs the kernel path; controller nudges toward k
    res_warm, t2 = gaussian_fused_compress(acc, k, t_cold, density=0.001)
    _ef_ok(acc, res_warm)
    nsel = int(res_warm.num_selected)
    if nsel > k:            # controller moves against the count error
        assert float(t2) > float(t_cold)
    elif nsel < k:
        assert float(t2) < float(t_cold)
    else:                   # exactly on target: threshold holds
        assert float(t2) == float(t_cold)


def test_k_beyond_candidate_capacity_is_refused():
    # direct call with k >> ceil(density*n): the geometry cannot hold k
    # candidates. The gate raises — no quiet route to another selector
    # under this one's name (the registry renames the spec above the
    # density ceiling; below it k = ceil(density*n) always fits)
    n = rows_per_block(0.001) * _LANES
    acc = _acc(n, seed=4)
    _, _, _, nc = _chunk_geometry(n, 0.001)
    with pytest.raises(ValueError, match="exceeds candidate capacity"):
        gaussian_fused_compress(acc, nc + 1, jnp.float32(0.1),
                                density=0.001)
    with pytest.raises(ValueError, match="exceeds candidate capacity"):
        gaussian_fused_compress_batched(acc[None], nc + 1,
                                        jnp.full((1,), 0.1), density=0.001)
    with pytest.raises(ValueError, match="supports density"):
        gaussian_fused_compress(acc, 8, jnp.float32(0.1), density=0.5)


def test_chunked_candidates_match_flat_per_chunk():
    """The chunked grid (uniform-plan path) must equal per-chunk flat calls:
    same candidates, same chunk-local indices, same exact counts — chunk
    boundaries are invisible to the extraction."""
    n_chunks, chunk = 3, 40_000          # ragged: chunk pads to a block
    rng = np.random.default_rng(7)
    x2d = jnp.asarray(rng.normal(0, 1, (n_chunks, chunk)), jnp.float32)
    ts = jnp.asarray([2.0, 2.5, 3.0], jnp.float32)   # distinct thresholds
    vals, idxs, counts = fused_select_candidates_chunked(x2d, ts,
                                                         density=0.01)
    for c in range(n_chunks):
        fv, fi, fc = fused_select_candidates(x2d[c], ts[c], density=0.01)
        assert int(counts[c]) == int(fc)
        order = np.lexsort((np.asarray(fi), np.asarray(fv)))
        order_c = np.lexsort((np.asarray(idxs[c]), np.asarray(vals[c])))
        np.testing.assert_array_equal(np.asarray(vals[c])[order_c],
                                      np.asarray(fv)[order])
        np.testing.assert_array_equal(np.asarray(idxs[c])[order_c],
                                      np.asarray(fi)[order])


def test_small_chunk_caps_reduction_span():
    """density <= 0.002 nominally picks R=1024, but a chunk smaller than
    1024 rows must cap R at its own row count (code-review r5: otherwise
    every chunk pads to a full 131072-element block and the kernel reads
    up to 4x zeros) — in a geometry Mosaic accepts: SEG halves until the
    candidate tile [R/SEG, 128] has 8 sublanes. With the cap the geometry
    still emits every above-threshold entry, with chunk-local indices."""
    chunk = 32_768                       # 256 rows < R=1024
    R, seg, bpc, nc = _chunk_geometry(chunk, 0.001)
    assert R == 256 and seg == 32 and bpc == 1
    assert nc == (R // seg) * _LANES
    assert _chunk_geometry(8192, 0.001)[:3] == (64, 8, 1)      # tight
    assert _chunk_geometry(65_536, 0.001)[:3] == (512, 64, 1)  # tight

    rng = np.random.default_rng(23)
    x_np = rng.normal(0, 0.5, (2, chunk)).astype(np.float32)  # below t
    for c in range(2):
        hot = _distinct_cell_indices(chunk, 40, 0.001)
        x_np[c, hot] = (rng.uniform(4.0, 8.0, 40)
                        * rng.choice([-1, 1], 40))
    x2d = jnp.asarray(x_np)
    ts = jnp.asarray([3.3, 3.4], jnp.float32)
    vals, idxs, counts = fused_select_candidates_chunked(x2d, ts,
                                                         density=0.001)
    assert vals.shape == (2, nc)
    for c in range(2):
        a = np.asarray(x2d[c])
        want = set(np.flatnonzero(np.abs(a) > float(ts[c])))
        v = np.asarray(vals[c])
        got = set(np.asarray(idxs[c])[v != 0])
        assert got == want                       # nothing lost to padding
        assert int(counts[c]) == len(want)


def test_batched_fused_warm_selection_and_ef():
    """Warm-path batched form: per-chunk magnitude selection at carried
    thresholds, exact EF per chunk, per-lane controller movement."""
    from gaussiank_sgd_tpu.compressors.gaussian import (
        gaussian_warm_compress_batched)

    n_chunks, chunk, k = 2, 60_000, 600
    rng = np.random.default_rng(11)
    # above-threshold entries in pairwise-distinct cells (the one-slot cap
    # cannot fire — overflow deferral is covered by
    # test_cell_overflow_defers_to_residual), count ~400 inside the warm
    # band [k/4, 4k]: fused and warm then select the IDENTICAL set
    x_np = rng.normal(0, 0.3, (n_chunks, chunk)).astype(np.float32)
    for c in range(n_chunks):
        hot = _distinct_cell_indices(chunk, 400, 0.01)
        x_np[c, hot] = (rng.uniform(3.0, 8.0, 400)
                        * rng.choice([-1, 1], 400))
    x = jnp.asarray(x_np)
    state = jnp.asarray([2.0, 2.1], jnp.float32)
    res, t_new = gaussian_fused_compress_batched(x, k, state,
                                                 density=0.01)
    ref, t_ref = gaussian_warm_compress_batched(x, k, state, density=0.01)
    for c in range(n_chunks):
        fi = np.asarray(res.compressed.indices[c])
        fv = np.asarray(res.compressed.values[c])
        ri = np.asarray(ref.compressed.indices[c])
        rv = np.asarray(ref.compressed.values[c])
        assert set(fi[fv != 0]) == set(ri[rv != 0])
        # exact EF per chunk
        sent = np.zeros(chunk, np.float32)
        np.add.at(sent, fi, fv)
        np.testing.assert_allclose(
            sent + np.asarray(res.residual[c]), np.asarray(x[c]),
            rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(t_new), np.asarray(t_ref),
                               rtol=1e-6)


def test_batched_fused_cold_lane_recovery():
    """One cold lane (state 0) must bootstrap its threshold from its own
    k-th candidate magnitude (_controller_update — the branch-free r5
    design has no bisection/recovery path) WITHOUT disturbing the warm
    lane's carried threshold trajectory."""
    n_chunks, chunk, k = 2, 60_000, 600
    rng = np.random.default_rng(13)
    x = jnp.asarray(rng.normal(0, 1, (n_chunks, chunk)), jnp.float32)
    state = jnp.asarray([2.6, 0.0], jnp.float32)     # lane 1 cold
    res, t_new = gaussian_fused_compress_batched(x, k, state, density=0.01)
    assert float(t_new[1]) > 0                        # cold lane recovered
    # warm lane: controller-only update from ITS carried threshold
    nsel0 = int(res.num_selected[0])
    assert (float(t_new[0]) > 2.6) == (nsel0 > k) or nsel0 == k
    for c in range(n_chunks):
        sent = np.zeros(chunk, np.float32)
        np.add.at(sent, np.asarray(res.compressed.indices[c]),
                  np.asarray(res.compressed.values[c]))
        np.testing.assert_allclose(
            sent + np.asarray(res.residual[c]), np.asarray(x[c]),
            rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("case", ["apart", "close_filled", "close_unfilled",
                                  "nothing_sent", "one_outlier"])
def test_the_controllers_step_follows_the_spread_of_what_was_sent(case):
    """Sent magnitudes that lie apart: the plain step, ``(count / k) **
    gain`` from the carried threshold, to the bit. Close together: the
    exponent is 0.7 of their mean share above what they lie above (the
    k-th of them where all slots were filled, the threshold where not),
    and where the slots were filled the step starts, by as much as the
    exponent fell short of ``gain``, from the k-th sent magnitude."""
    from gaussiank_sgd_tpu.ops.pallas_pack import _controller_update
    k, gain, t = 4, 0.18, 1.0
    vals, valid, count = {
        "apart": ([1.3, -1.1, 2.0, 1.2], [True] * 4, 80),
        "close_filled": ([1.03, -1.02, 1.05, 1.04], [True] * 4, 80),
        "close_unfilled": ([1.03, -1.02, 0.0, 0.0], [True, True, False,
                                                     False], 2),
        "nothing_sent": ([0.0] * 4, [False] * 4, 0),
        "one_outlier": ([7.0, 0.0, 0.0, 0.0], [True, False, False, False], 1),
    }[case]
    got = float(_controller_update(
        jnp.asarray([t], jnp.float32), jnp.asarray([count], jnp.int32),
        jnp.asarray([vals], jnp.float32), jnp.asarray([valid]), k, gain)[0])
    ratio = (count + 1.0) / (k + 1.0)
    plain = float(np.float32(t) * np.clip(np.float32(ratio) ** np.float32(
        gain), 0.25, 4.0))
    sent = np.abs([v for v, ok in zip(vals, valid) if ok])
    if case in ("apart", "nothing_sent", "one_outlier"):
        assert got == pytest.approx(plain, rel=1e-6)
        return
    base = sent.min() if case == "close_filled" else t
    g = 0.7 * (sent.mean() / base - 1.0)
    assert 0 < g < gain / 4
    want = t * ratio ** g * (base / t) ** (1.0 - g / gain)
    assert got == pytest.approx(want, rel=1e-5)
    # a far smaller step than the plain one, on the plain one's side of t
    assert abs(np.log(got)) < abs(np.log(plain)) / 4
    assert (got > t) == (plain > t)


@pytest.mark.parametrize("sharp", [True, False])
def test_the_selection_settles_on_a_gradient_under_error_feedback(sharp):
    """One population of like entries beside a quiet rest (`sharp`), or
    magnitudes that lie apart, each accumulated by error feedback. On the
    first the plain step (exponent ``gain`` whatever was sent) falls into a
    cycle within 20 steps at this size (20 k selected, then none and 15 of
    263 entries sent, and round again: PERF.md section 6, PR 33); the
    controller's own reads inside [0.5, 2.5] k from the tenth step on and
    every step sends 200 of its 263 at least, on both gradients."""
    from gaussiank_sgd_tpu.compressors.registry import get_compressor
    spec = get_compressor("gaussian_fused")
    n, k = 64 * 4096, 263
    rng = np.random.default_rng(0)
    if sharp:
        scale = np.full(n, 0.05, np.float32)
        scale[: n // 4] = 1.0
    else:
        scale = np.exp(rng.normal(size=n)).astype(np.float32)
    res = jnp.zeros((1, n), jnp.float32)
    state = jnp.zeros((1,), jnp.float32)
    ratios, sent = [], []
    for _ in range(50):
        g = jnp.asarray(rng.normal(size=(1, n)) * scale, jnp.float32)
        out, state = spec.batched_fn(res + g, k, state)
        res = out.residual
        ratios.append(float(out.num_selected[0]) / k)
        sent.append(int(np.count_nonzero(np.asarray(
            out.compressed.values[0]))))
    assert min(sent[10:]) >= 0.7 * k, sent
    assert 0.5 <= min(ratios[10:]) and max(ratios[10:]) <= 2.5, ratios


def test_uniform_plan_takes_kernel_path():
    """The registry's gaussian_fused batched_fn IS the chunked kernel form
    (VERDICT r4 item 3: no silent downgrade on uniform plans), and the
    full compress_buckets uniform path preserves EF through it."""
    from gaussiank_sgd_tpu.compressors import get_compressor
    from gaussiank_sgd_tpu.parallel.bucketing import make_bucket_plan
    from gaussiank_sgd_tpu.parallel.trainstep import compress_buckets

    spec = get_compressor("gaussian_fused", density=0.01)
    assert spec.batched_fn is not None
    assert spec.batched_fn.func is gaussian_fused_compress_batched

    n = 100_000
    plan = make_bucket_plan([n], density=0.01, bucket_size=32_768,
                            policy="uniform")
    assert plan.uniform and len(plan.buckets) > 1
    acc = _acc(n, seed=17)
    st = jnp.full((len(plan.buckets),), 2.6, jnp.float32)
    comp, residual, nsel, st_new = compress_buckets(
        spec, plan, acc, jax.random.PRNGKey(0), st)
    # global EF invariant across chunk offsets
    sent = np.zeros(n, np.float32)
    np.add.at(sent, np.asarray(comp.indices), np.asarray(comp.values))
    np.testing.assert_allclose(sent + np.asarray(residual),
                               np.asarray(acc), rtol=1e-6, atol=1e-6)
    assert st_new.shape == st.shape and not np.array_equal(
        np.asarray(st_new), np.asarray(st))


def test_registry_entry_and_train_step():
    """gaussian_fused drives the full SPMD sparse step on the 8-way mesh."""
    import optax

    from gaussiank_sgd_tpu.compressors import get_compressor
    from gaussiank_sgd_tpu.parallel.bucketing import make_bucket_plan
    from gaussiank_sgd_tpu.parallel.mesh import (data_parallel_mesh,
                                                 shard_batch)
    from gaussiank_sgd_tpu.parallel.trainstep import build_dp_train_step

    spec = get_compressor("gaussian_fused", density=0.01)
    assert spec.stateful and spec.name == "gaussian_fused"

    dim, nout = 64, 4
    def loss_fn(params, mstate, batch, rng):
        x, y = batch
        logits = x @ params["w"] + params["b"]
        one = jax.nn.one_hot(y, nout)
        return jnp.mean((logits - one) ** 2), (mstate, {})

    mesh = data_parallel_mesh()
    params = {"w": jnp.zeros((dim, nout)), "b": jnp.zeros((nout,))}
    plan = make_bucket_plan([dim * nout + nout], density=0.01)
    ts = build_dp_train_step(loss_fn, optax.sgd(0.1), spec, plan, mesh)
    state = ts.init_state(params, jax.random.PRNGKey(0), model_state={})
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(16, dim)), jnp.float32)
    y = jnp.asarray(rng.integers(0, nout, size=(16,)))
    batch = shard_batch(mesh, (x, y))
    losses = []
    for _ in range(6):
        state, m = ts.sparse_step(state, batch)
        losses.append(float(m.loss))
    assert losses[-1] < losses[0]          # actually learns through the kernel
    assert int(state.step) == 6


# the candidate buffers of the two one-chip benchmark cells (VGG-16 and
# ResNet-50 at density 0.001: nc = padded n / 64), a uniform bucket's, and
# one whose k output slots are read in two blocks (k > _SLOT_BLOCK)
_SELECT_SHAPES = [(1024, 66), (235_520, 14_987), (399_360, 25_558),
                  (131_072, 40_000)]
_SELECT_CASES = ["half", "k", "2k", "cold", "dead", "ties", "crowded",
                 "vmap3"]


def _candidates(case, nc, k, n, seed):
    """A candidate buffer as the kernel writes one: (value, index) per
    slot, (0, 0) where the slot holds nothing; valid indices distinct."""
    rng = np.random.default_rng(seed)
    count = {"half": k // 2, "k": k, "2k": 2 * k, "cold": nc, "dead": 0,
             "ties": 2 * k, "crowded": 2 * k}[case]
    vals = np.zeros(nc, np.float32)
    if case == "crowded":       # every lane of the first few rows
        pos = np.arange(count)
    else:
        pos = rng.permutation(nc)[:count]
    mag = rng.uniform(1e-3, 1.0, count).astype(np.float32)
    if case == "ties":          # 40 equal magnitudes astride the k-th
        order = np.argsort(-mag)
        mag[order[k - 20:k + 20]] = mag[order[k]]
    vals[pos] = mag * rng.choice(np.float32([-1, 1]), count)
    idxs = np.zeros(nc, np.int32)
    idxs[pos] = rng.permutation(n)[:count]
    return vals, idxs


def _top_k_pairs(vals, idxs, k):
    """The contract: ``lax.top_k`` over the magnitudes, zeros left out,
    as a set of (index, float32 bit pattern)."""
    kv, kpos = jax.lax.top_k(jnp.abs(jnp.asarray(vals)), k)
    kpos = np.asarray(kpos)[np.asarray(kv) > 0]
    return set(zip(idxs[kpos].tolist(),
                   vals[kpos].view(np.uint32).tolist()))


@pytest.mark.parametrize("case", _SELECT_CASES)
@pytest.mark.parametrize("nc,k", _SELECT_SHAPES)
def test_selection_is_top_k_of_the_candidates(nc, k, case):
    """What is sent is the k valid candidates of largest magnitude (all of
    them when fewer are valid), bit for bit, invalid slots (n, 0), and
    the rest stays in the residual — at the benchmark cells' own shapes."""
    from gaussiank_sgd_tpu.ops.pallas_pack import (_pack_candidates,
                                                   _select_candidates_topk)
    n = 4 * nc
    select = jax.jit(_select_candidates_topk, static_argnums=(2, 3))
    if case == "vmap3":         # as the batched forms call it
        chunks = [_candidates(c, nc, k, n, seed)
                  for seed, c in enumerate(["k", "cold", "dead"])]
        sent, val = jax.jit(jax.vmap(
            lambda v, i: _select_candidates_topk(v, i, k, n)))(
                jnp.stack([v for v, _ in chunks]),
                jnp.stack([i for _, i in chunks]))
    else:
        chunks = [_candidates(case, nc, k, n, seed=nc)]
        sent, val = (a[None] for a in select(*chunks[0], k, n))
    sent, val = np.asarray(sent), np.asarray(val)
    assert sent.shape == val.shape == (len(chunks), k)
    for (vals, idxs), s, v in zip(chunks, sent, val):
        valid = s < n
        assert (s[~valid] == n).all() and (v[~valid] == 0).all()
        want = _top_k_pairs(vals, idxs, k)
        assert valid.sum() == len(want) == min(k, (vals != 0).sum())
        assert set(zip(s[valid].tolist(),
                       v[valid].view(np.uint32).tolist())) == want
    if case != "vmap3":
        # what was passed over is still in the residual after finish_pack
        vals, idxs = chunks[0]
        buf = np.zeros(n, np.float32)
        buf[idxs[vals != 0]] = vals[vals != 0]
        comp, residual = jax.jit(_pack_candidates, static_argnums=3)(
            vals, idxs, jnp.asarray(buf), k)
        dense = np.zeros(n, np.float32)
        np.add.at(dense, np.asarray(comp.indices), np.asarray(comp.values))
        assert np.array_equal(dense + np.asarray(residual), buf)
        assert np.count_nonzero(np.asarray(residual)) == (
            (vals != 0).sum() - len(want))


def test_ef_padded_chunk_geometry():
    from gaussiank_sgd_tpu.ops.pallas_pack import (_chunk_geometry,
                                                   ef_padded_chunk)

    # block-aligned suffix pad at supported density
    cp = ef_padded_chunk(100_000, 100, density=0.001)
    R, _, bpc, _ = _chunk_geometry(100_000, 0.001)
    assert cp == bpc * R * _LANES and cp >= 100_000
    # an already-aligned uniform chunk maps to itself (multi-chunk
    # eligibility: offsets unchanged)
    assert ef_padded_chunk(32_768, 32, density=0.001) == 32_768
    # unsupported density / over-capacity k -> None (unfused fallback)
    assert ef_padded_chunk(100_000, 100, density=0.5) is None
    _, _, _, nc = _chunk_geometry(100_000, 0.001)
    assert ef_padded_chunk(100_000, nc + 1, density=0.001) is None


def test_fused_ef_matches_unfused_on_same_acc():
    """The EF+select kernel must select the same set, produce the same
    controller update, and the same residual (to accumulate rounding — the
    kernel may fuse res + scale*g into an FMA) as the unfused batched form
    run on a precomputed acc."""
    from gaussiank_sgd_tpu.ops.pallas_pack import (
        ef_padded_chunk, gaussian_fused_ef_compress_batched)

    rng = np.random.default_rng(29)
    n, density = 50_000, 0.01
    k = max(1, int(np.ceil(density * n)))
    cp = ef_padded_chunk(n, k, density=density)
    res = np.zeros((1, cp), np.float32)
    res[0, :n] = rng.normal(0, 0.1, n).astype(np.float32)
    g = np.zeros((1, cp), np.float32)
    g[0, :n] = rng.normal(0, 1, n).astype(np.float32)
    state = jnp.asarray([0.5], jnp.float32)
    scale = jnp.float32(0.3)

    r, t_new = gaussian_fused_ef_compress_batched(
        jnp.asarray(res), jnp.asarray(g), scale, k, state, density=density)
    acc = jnp.asarray(res) + scale * jnp.asarray(g)
    r_ref, t_ref = gaussian_fused_compress_batched(acc, k, state,
                                                   density=density)
    fi = np.asarray(r.compressed.indices[0])
    fv = np.asarray(r.compressed.values[0])
    ri = np.asarray(r_ref.compressed.indices[0])
    rv = np.asarray(r_ref.compressed.values[0])
    assert set(fi[fv != 0]) == set(ri[rv != 0])
    np.testing.assert_allclose(np.asarray(t_new), np.asarray(t_ref),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(r.residual),
                               np.asarray(r_ref.residual),
                               rtol=0, atol=1.5e-7)
    assert int(r.num_selected[0]) == int(r_ref.num_selected[0])


def test_fused_ef_exact_bookkeeping_and_inert_pad():
    """EF exactness against the kernel's own accumulator: residual +
    scatter(sent) == res + scale*g, and the pad region stays exactly zero
    (thresholds >= 0, strict > mask) — the invariant the padded live
    buffer contract rests on."""
    from gaussiank_sgd_tpu.ops.pallas_pack import (
        ef_padded_chunk, gaussian_fused_ef_compress_batched)

    rng = np.random.default_rng(31)
    n, density = 70_001, 0.01                       # ragged size
    k = max(1, int(np.ceil(density * n)))
    cp = ef_padded_chunk(n, k, density=density)
    res = np.zeros((1, cp), np.float32)
    res[0, :n] = rng.normal(0, 0.2, n).astype(np.float32)
    g = np.zeros((1, cp), np.float32)
    g[0, :n] = rng.normal(0, 1, n).astype(np.float32)
    state = jnp.asarray([0.8], jnp.float32)
    r, _t = gaussian_fused_ef_compress_batched(
        jnp.asarray(res), jnp.asarray(g), jnp.float32(1.0), k, state,
        density=density)
    rec = np.asarray(r.residual[0]).copy()
    idx = np.asarray(r.compressed.indices[0])
    val = np.asarray(r.compressed.values[0])
    ok = idx < cp                                   # sentinel slots invalid
    np.add.at(rec, idx[ok], val[ok])
    np.testing.assert_allclose(rec, res[0] + g[0], rtol=1e-6, atol=1e-6)
    # inert pad: nothing selected there, residual pad exactly zero
    assert not np.asarray(r.residual[0, n:]).any()
    assert (idx[ok] < n).all()


def test_fused_ef_rejects_unaligned_chunks():
    from gaussiank_sgd_tpu.ops.pallas_pack import (
        gaussian_fused_ef_compress_batched)

    x = jnp.zeros((1, 5000), jnp.float32)           # not block-aligned
    with pytest.raises(ValueError, match="pre-padded"):
        gaussian_fused_ef_compress_batched(
            x, x, jnp.float32(1.0), 50, jnp.zeros((1,), jnp.float32),
            density=0.01)
