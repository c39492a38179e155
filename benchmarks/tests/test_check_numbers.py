"""The numbers of `check.py` that PR 27 re-made for configurations of
hundreds of millions of parameters (sums over blocks in float64 with no
whole-leaf float64 copy, the program's readings a block at a time on
threads, `lost_entries`' threshold from a few long stretches): against the
functions they replace (`old_check.py`, word for word), on seeded states of
the tiny cells. Equal to 1e-12 relative where a float64 sum is now taken
over blocks, bit for bit everywhere else."""

import copy
import types

import numpy as np
import pytest

import old_check as OLD
from benchmarks import check, harness


@pytest.fixture(scope="module", params=["tiny_dp1", "tiny_dp4"])
def seeded(request, tiny_root):
    """One sound set of first-step readings of a tiny cell (one device or
    four) with the reference's readings of the same steps."""
    cell = harness.load_cell(request.param, root=tiny_root)
    out_dir = harness.make_out_dir()
    try:
        arms, weights = harness.build_arms(cell, 13, out_dir, False)
        for arm in arms.values():
            harness.first_steps(arm, cell["config_data"])
        harness.warm_up(arms["sparse"], cell["mix"])
        firsts = {n: types.SimpleNamespace(name=n, first=a.first)
                  for n, a in arms.items()}
        harness.close_arms(arms)
    finally:
        harness.remove_out_dir(out_dir)
    config, mix = cell["config_data"], cell["mix"]
    ref = check.reference_readings(
        config, mix, 13, {n: a.first["batches"] for n, a in firsts.items()},
        firsts["sparse"].first["masks"], weights)
    return cell, firsts, weights, ref


def same(new, old, what=""):
    """1e-12 of the number; where the number is a gap between two norms
    that all but agree (5.8e-5 of them in the dense delta), 1e-14 of the
    norms, which is what a sum's last bits can move it by."""
    if isinstance(old, str):
        assert new == old, what
    else:
        assert new == pytest.approx(old, rel=1e-12, abs=1e-14), what


@pytest.mark.parametrize("block", [1000, 1 << 20])
def test_the_programs_readings_are_the_old_ones_bit_for_bit(
        seeded, block, monkeypatch):
    monkeypatch.setattr(harness, "BLOCK", block)
    cell, firsts, weights, _ = seeded
    for arm in firsts.values():
        new = check.program_readings(arm, weights, cell["config_data"])
        old = OLD.program_readings(arm, weights, cell["config_data"])
        assert set(new) == set(old)
        assert new["losses"] == old["losses"]
        for q in ("first_grad", "delta"):
            assert list(new[q]) == list(old[q])
            for p in old[q]:
                assert new[q][p].shape == old[q][p].shape
                np.testing.assert_array_equal(new[q][p], old[q][p], p)
        if arm.name == "sparse":
            assert new["exact"] == old["exact"]
            np.testing.assert_array_equal(new["zeroed1"], old["zeroed1"])
            np.testing.assert_array_equal(new["sent_any1"], old["sent_any1"])


@pytest.mark.parametrize("block", [1000, 1 << 20])
def test_compare_and_lost_entries_read_what_the_old_ones_read(
        seeded, block, monkeypatch):
    monkeypatch.setattr(harness, "BLOCK", block)
    cell, firsts, weights, ref = seeded
    config = cell["config_data"]
    mine = {n: check.program_readings(a, weights, config)
            for n, a in firsts.items()}
    head = config.get("head_leaf") or "Dense_1/kernel"
    table = {}
    new = check.compare(mine, ref, head, table=table)
    old = OLD.compare(mine, ref, head)
    assert list(new) == list(old)
    for key in old:
        same(new[key], old[key], key)
    assert new["head_grad_rel_err"] > 0 and new["delta_norm_gap"] > 0
    old_table = OLD.leaf_table(mine, ref)
    assert set(table) == set(old_table)
    for q in old_table:
        assert list(table[q]) == list(old_table[q])
        for p in old_table[q]:
            same(table[q][p], old_table[q][p], f"{q} {p}")
    k = int(firsts["sparse"].first["k"])
    assert check.lost_entries(mine["sparse"], ref["sparse"], k) == \
        OLD.lost_entries(mine["sparse"], ref["sparse"], k) == 0


def test_a_doctored_state_reads_the_old_numbers_too(seeded):
    """Half of what arrived taken out of the momentum again: `lost`, the
    norms and the errors all move, and move alike in both."""
    cell, firsts, weights, ref = seeded
    config = cell["config_data"]
    doctored = copy.deepcopy(firsts)
    f = doctored["sparse"].first
    like = f["params"]
    quiet = np.float32(config["trainer"]["weight_decay"]) * np.concatenate(
        [weights[p].reshape(-1) for p in like])
    sent = np.flatnonzero(f["momentum1"][:quiet.size] != quiet)
    f["momentum1"][sent[::2]] = quiet[sent[::2]]
    mine = {"sparse": check.program_readings(doctored["sparse"], weights,
                                             config)}
    only = {"sparse": ref["sparse"]}
    new, old = check.compare(mine, only), OLD.compare(mine, only)
    for key in old:
        same(new[key], old[key], key)
    k = int(f["k"])
    lost = check.lost_entries(mine["sparse"], ref["sparse"], k)
    assert lost == OLD.lost_entries(mine["sparse"], ref["sparse"], k) > 0


def test_an_all_zero_leaf_has_no_gap():
    """A leaf whose gradient is exactly zero on both sides (embedding rows
    that no batch names make whole leaves of it in a small model)."""
    zero = {"a": np.zeros((7, 3), np.float32),
            "b": np.ones((5,), np.float32)}
    sums = check.leaf_sums(zero, zero)
    assert sums["a"] == (0.0, 0.0, 0.0)
    assert check.leaf_norm_gap(sums) == OLD.leaf_norm_gap(zero, zero) == \
        (0.0, "")
    assert check.rel_err(sums) == OLD.rel_err(zero, zero) == 0.0
    only = check.leaf_sums({"a": zero["a"]}, {"a": zero["a"]})
    assert check.leaf_norm_gap(only) == (0.0, "")
    assert check.rel_err(only) == OLD.rel_err(
        {"a": zero["a"]}, {"a": zero["a"]}) == float("inf")


@pytest.mark.parametrize("shape", [(3_000_000,), (4, 700_000)])
def test_mantissa_distance_samples_the_values_the_old_one_sampled(
        shape, monkeypatch):
    monkeypatch.setattr(harness, "BLOCK", 65_536 + 7)
    rng = np.random.default_rng(2)
    v = rng.standard_normal(shape).astype(np.float32)
    v[rng.random(shape) < 0.3] = 0.0
    for sample in (1 << 10, 1 << 14, 1 << 30):
        assert check.mantissa_distance(v, sample) == \
            OLD.mantissa_distance(v, sample) > 0
    assert check.mantissa_distance(np.zeros(shape, np.float32)) == 0.0
    part = v[..., :-5]          # a slice, as the residual without its pad
    assert check.mantissa_distance(part, 1 << 12) == \
        OLD.mantissa_distance(part, 1 << 12)


def test_lost_entries_finds_the_threshold_in_stretches(monkeypatch):
    """k small against n: eight stretches, one a thread. The threshold is
    the 2k-th largest magnitude either way, ties included."""
    monkeypatch.setattr(harness, "BLOCK", 4099)
    n, k, rng = 200_000, 10, np.random.default_rng(4)
    assert min(harness.THREADS, n // (8 * 2 * k)) == harness.THREADS
    g = [rng.standard_normal(n).astype(np.float32) for _ in range(2)]
    g[1][:5000] = np.float32(9.0)             # ties at the top
    zeroed = rng.random((2, n)) < 0.5
    sent = rng.random(n) < 0.5
    mine = {"zeroed1": zeroed, "sent_any1": sent}
    ref = {"first_grad_workers": g}
    got = check.lost_entries(mine, ref, k)
    assert got == OLD.lost_entries(mine, ref, k) > 0
