"""Compressor unit tests against NumPy oracles (SURVEY.md §4 test plan (a)).

Covers: TopK selection exactness, GaussianK tail/count bounds, EF mass
conservation (sent + residual == acc elementwise), fixed-k packing under
truncation and padding, and decompress round-trips — for every registry entry.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gaussiank_sgd_tpu.compressors import (CompressResult, decompress,
                                           get_compressor, k_for, NAMES,
                                           pack_by_threshold)

def _acc(n=4096, scale=1.0, dist="normal", seed=0):
    # fresh generator per call: test data must not depend on execution order
    rng = np.random.default_rng(seed)
    if dist == "normal":
        a = rng.normal(0.0, scale, size=n)
    elif dist == "laplace":  # heavy-tailed, the PTB-LSTM regime (BASELINE cfg 4)
        a = rng.laplace(0.0, scale, size=n)
    else:
        raise ValueError(dist)
    return jnp.asarray(a, jnp.float32)


def _check_ef_invariant(acc, res: CompressResult):
    """sent ⊎ residual == acc: every entry is either packed or in the residual."""
    acc = np.asarray(acc)
    dense_sent = np.zeros_like(acc)
    idx = np.asarray(res.compressed.indices)
    val = np.asarray(res.compressed.values)
    np.add.at(dense_sent, idx, val)
    np.testing.assert_allclose(dense_sent + np.asarray(res.residual), acc,
                               rtol=1e-6, atol=1e-6)
    # no index is packed twice with a nonzero value (padding dups are 0-valued)
    nz = val != 0
    assert len(np.unique(idx[nz])) == nz.sum()


def _call(spec, acc, k, rng=None):
    """Uniform invocation across stateless and stateful compressors
    (stateful fns take a state scalar and return (result, new_state))."""
    if spec.stateful:
        res, _ = spec.fn(acc, k, jnp.float32(spec.init_state), rng)
        return res
    return spec.fn(acc, k, rng)


@pytest.mark.parametrize("name", NAMES)
def test_ef_mass_conservation(name):
    spec = get_compressor(name, density=0.01)
    acc = _acc(2048)
    k = k_for(acc.size, 0.01)
    rng = jax.random.PRNGKey(1) if spec.requires_rng else None
    res = _call(spec, acc, k, rng)
    want_k = acc.size if spec.out_k is None else spec.out_k(k)
    assert res.compressed.indices.shape == (want_k,)
    assert res.compressed.values.shape == (want_k,)
    if spec.uses_error_feedback or spec.name == "none":
        _check_ef_invariant(acc, res)
    else:
        # randomk discards the un-sent mass: residual must be all zero
        assert not np.any(np.asarray(res.residual))


def test_topk_matches_numpy_oracle():
    spec = get_compressor("topk")
    acc = _acc(1000)
    k = 37
    res = spec.fn(acc, k, None)
    oracle_idx = np.argsort(-np.abs(np.asarray(acc)), kind="stable")[:k]
    assert set(np.asarray(res.compressed.indices).tolist()) == set(
        oracle_idx.tolist())
    # residual zero exactly at selected positions
    r = np.asarray(res.residual)
    assert np.all(r[oracle_idx] == 0)
    mask = np.ones(1000, bool)
    mask[oracle_idx] = False
    np.testing.assert_array_equal(r[mask], np.asarray(acc)[mask])


@pytest.mark.parametrize("dist", ["normal", "laplace"])
@pytest.mark.parametrize("density", [0.001, 0.01, 0.1])
def test_gaussiank_count_near_k(dist, density):
    """After refinement the selected count must be close to k even when the
    Gaussian model is wrong (laplace = BASELINE config 4's regime)."""
    spec = get_compressor("gaussian", density=density)
    n = 65536
    acc = _acc(n, dist=dist)
    k = k_for(n, density)
    res = spec.fn(acc, k, None)
    m = int(res.num_selected)
    assert 0 < m, "threshold selected nothing"
    assert m <= 2.0 * k + 8, f"selected {m} vs k={k}: refinement failed high"
    assert m >= 0.4 * k, f"selected {m} vs k={k}: refinement failed low"
    # packed values must be the largest-|.|-ish entries: all packed magnitudes
    # >= the threshold implied by the weakest packed value minus refinement slop
    val = np.asarray(res.compressed.values)
    nz = val[val != 0]
    a = np.abs(np.asarray(acc))
    kth = np.sort(a)[-k]
    assert np.min(np.abs(nz)) >= 0.25 * kth


def test_gaussiank_matches_topk_on_clean_gaussian():
    """On a big clean Gaussian, GaussianK's pick overlaps heavily with TopK."""
    n = 1 << 16
    density = 0.01
    acc = _acc(n)
    k = k_for(n, density)
    g = get_compressor("gaussian", density=density).fn(acc, k, None)
    t = get_compressor("topk").fn(acc, k, None)
    gi = set(np.asarray(g.compressed.indices)[
        np.asarray(g.compressed.values) != 0].tolist())
    ti = set(np.asarray(t.compressed.indices).tolist())
    overlap = len(gi & ti) / k
    assert overlap > 0.8, f"GaussianK/TopK overlap {overlap:.2f}"


def test_pack_truncation_and_padding():
    acc = jnp.asarray([5.0, -4.0, 3.0, -2.0, 1.0, 0.5], jnp.float32)
    # threshold 0.75 selects 5 entries; k=3 keeps lowest-index-first 3
    res = pack_by_threshold(acc, jnp.float32(0.75), 3)
    np.testing.assert_array_equal(res.compressed.indices, [0, 1, 2])
    np.testing.assert_allclose(res.compressed.values, [5.0, -4.0, 3.0])
    assert int(res.num_selected) == 5
    # truncated entries (3, 4) stay in the residual — EF exactness
    np.testing.assert_allclose(res.residual, [0, 0, 0, -2.0, 1.0, 0.5])
    # threshold 4.5 selects 1 entry; k=3 pads with (0, 0)
    res = pack_by_threshold(acc, jnp.float32(4.5), 3)
    np.testing.assert_array_equal(res.compressed.indices, [0, 0, 0])
    np.testing.assert_allclose(res.compressed.values, [5.0, 0, 0])
    dense = decompress(res.compressed, 6)
    np.testing.assert_allclose(dense, [5.0, 0, 0, 0, 0, 0])


def test_randomk_aligned_across_identical_keys():
    """Same PRNG key -> same index set: the SPMD alignment the reference gets
    from shared seeds (SURVEY.md §2.3 RandomK)."""
    spec = get_compressor("randomk")
    acc1, acc2 = _acc(512, seed=1), _acc(512, seed=2)
    r1 = spec.fn(acc1, 16, jax.random.PRNGKey(7))
    r2 = spec.fn(acc2, 16, jax.random.PRNGKey(7))
    np.testing.assert_array_equal(r1.compressed.indices, r2.compressed.indices)
    # distinct indices (sampling without replacement)
    assert len(set(np.asarray(r1.compressed.indices).tolist())) == 16


def test_redsync_count_in_band():
    spec = get_compressor("redsync")
    n = 16384
    acc = _acc(n)
    k = k_for(n, 0.01)
    res = spec.fn(acc, k, None)
    m = int(res.num_selected)
    assert k <= m <= 2 * k + 4, f"redsync count {m} outside [k, 2k], k={k}"
    assert res.compressed.values.shape == (2 * k,)


def test_dgc_selects_heavy_entries():
    spec = get_compressor("dgcsampling", density=0.01)
    n = 8192
    acc = _acc(n)
    k = k_for(n, 0.01)
    res = spec.fn(acc, k, jax.random.PRNGKey(3))
    val = np.asarray(res.compressed.values)
    nz = np.abs(val[val != 0])
    assert nz.size > 0
    a = np.abs(np.asarray(acc))
    kth = np.sort(a)[-k]
    assert np.median(nz) >= 0.5 * kth


@pytest.mark.parametrize("name", NAMES)
def test_compressors_jit_with_static_shapes(name):
    spec = get_compressor(name, density=0.01)
    acc = _acc(1024)
    k = k_for(acc.size, 0.01)
    rng = jax.random.PRNGKey(0) if spec.requires_rng else None
    jitted = jax.jit(lambda a, r: _call(spec, a, k, r))
    res = jitted(acc, rng)
    res2 = _call(spec, acc, k, rng)
    if name == "approxtopk16":
        # bf16 magnitude ranking: entries within one bf16 ulp can swap
        # between jit and eager (documented in exact.py); the invariant
        # that DOES hold is exact EF bookkeeping on both paths
        for r in (res, res2):
            _check_ef_invariant(acc, r)
        return
    np.testing.assert_allclose(res.compressed.values, res2.compressed.values,
                               rtol=1e-6)
    np.testing.assert_array_equal(res.compressed.indices,
                                  res2.compressed.indices)


def test_decompress_sums_duplicate_indices():
    """Multi-worker decompress must *sum* colliding indices (SURVEY.md §3.1)."""
    from gaussiank_sgd_tpu.compressors import CompressedGrad
    c = CompressedGrad(jnp.asarray([2, 2, 0], jnp.int32),
                       jnp.asarray([1.0, 2.0, 5.0], jnp.float32))
    np.testing.assert_allclose(decompress(c, 4), [5.0, 0.0, 3.0, 0.0])


def test_auto_resolves_through_the_registry_default():
    """The selector a user inherits is the codified ex-ante default
    (VERDICT r3 item 2): ``default_selector`` answers it for every model
    and ``--compressor auto`` resolves through the same policy."""
    from gaussiank_sgd_tpu.compressors import (DEFAULT_SELECTOR,
                                               default_selector)

    assert default_selector() == DEFAULT_SELECTOR
    assert default_selector("resnet50") == DEFAULT_SELECTOR
    assert get_compressor("auto").name == \
        get_compressor(DEFAULT_SELECTOR).name
