"""`harness.first_steps`, which PR 27 rewrote so that a configuration of
hundreds of millions of parameters finishes its set-up (the state's copies
to the host started together, the residual's zero test as one fused pass
on the device, the arrival test on threads): against the implementation it
replaces (kept here, word for word), bit for bit, on one device and on
four, the sparse trainer and the dense baseline."""

import numpy as np
import pytest

from benchmarks import harness
from benchmarks.harness import leaves_by_path, split_flat


def _host(x) -> np.ndarray:
    import jax
    return np.asarray(jax.device_get(x))


def first_steps_before_pr27(arm, config: dict, steps: int = 3) -> None:
    """The implementation up to PR 26: after every step the whole momentum
    and parameter vector go to the host one after the other, and the sent
    masks are made there in whole-vector numpy on one thread."""
    tr = arm.trainer
    n = tr.plan.total_numel
    nworkers = tr.mesh.size
    mu = np.float32(config["trainer"]["momentum"])
    wd = np.float32(config["trainer"]["weight_decay"])
    losses, masks = [], []
    arm.first["k"] = int(tr.plan.total_k)
    arm.first["built"] = {"wire_format": tr.ts.wire_format,
                          "kernel_mode": tr.ts.kernel_mode,
                          "buckets": len(tr.plan.buckets)}

    def flat_params():
        import jax
        leaves = jax.device_get(
            list(leaves_by_path(tr._state.params).values()))
        return np.concatenate([np.asarray(v).reshape(-1) for v in leaves])

    prev_m = np.zeros((n,), np.float32)
    prev_p = flat_params()
    for s in range(steps):
        rec = arm.train(1)
        losses.append(float(rec["loss"]))
        state = tr._state
        res = state.ef_residual.reshape(nworkers, -1)
        m = _host(state.opt_state["m"])[:n]
        if arm.name == "sparse":
            quiet = mu * prev_m + wd * prev_p
            arrived = np.abs(m - quiet) > 1e-5 * np.abs(quiet) + 1e-12
            masks.append(_host(res[:, :n] == 0) & arrived[None, :])
        if s == 0:
            arm.first["momentum1"] = m
            # the dense baseline's residual is allocated and never read
            arm.first["residual1"] = (_host(res) if arm.name == "sparse"
                                      else None)
            arm.first["dtypes"] = {
                "residual_dtype": str(state.ef_residual.dtype),
                "momentum_dtype": str(state.opt_state["m"].dtype)}
            arm.first["residual_devices"] = len(
                {d.id for d in state.ef_residual.sharding.device_set})
        prev_m, prev_p = m, flat_params()
    arm.first["losses"] = losses
    arm.first["masks"] = masks
    arm.first["params"] = split_flat(
        prev_p, leaves_by_path(tr._state.params))
    arm.first["batches"] = list(arm.feed.kept)


def firsts_of(tiny_root, cell_name, seed, fn):
    """Every arm of the cell built from the seed and taken through its
    first steps by `fn`; what `fn` left in `arm.first`."""
    cell = harness.load_cell(cell_name, root=tiny_root)
    out_dir = harness.make_out_dir()
    try:
        arms, _ = harness.build_arms(cell, seed, out_dir, False)
        for arm in arms.values():
            fn(arm, cell["config_data"])
        firsts = {n: a.first for n, a in arms.items()}
        harness.close_arms(arms)
    finally:
        harness.remove_out_dir(out_dir)
    return firsts


@pytest.mark.parametrize("cell_name,workers", [("tiny_dp1", 1),
                                               ("tiny_dp4", 4)])
def test_the_new_first_steps_read_what_the_old_ones_read(
        tiny_root, cell_name, workers, monkeypatch):
    """Two sets of trainers from one seed see the same rows and take the
    same steps; the host's arithmetic goes in blocks (here of 4099
    entries, the last one short) on threads."""
    monkeypatch.setattr(harness, "BLOCK", 4099)
    old = firsts_of(tiny_root, cell_name, 7, first_steps_before_pr27)
    new = firsts_of(tiny_root, cell_name, 7, harness.first_steps)
    assert set(new) == set(old) == {"dense", "sparse"}
    for arm in old:
        a, b = new[arm], old[arm]
        assert set(a) == set(b) | {"step_held_bytes"}
        assert a["losses"] == b["losses"] and len(a["losses"]) == 3
        for key in ("k", "built", "dtypes", "residual_devices"):
            assert a[key] == b[key], key
        np.testing.assert_array_equal(a["momentum1"], b["momentum1"])
        assert list(a["params"]) == list(b["params"])
        for p in b["params"]:
            assert a["params"][p].shape == b["params"][p].shape
            np.testing.assert_array_equal(a["params"][p], b["params"][p], p)
        for x, y in zip(a["batches"], b["batches"]):
            for u, v in zip(x, y):
                np.testing.assert_array_equal(u, v)
    a, b = new["sparse"], old["sparse"]
    assert a["residual1"].shape == b["residual1"].shape
    assert a["residual1"].shape[0] == workers
    np.testing.assert_array_equal(a["residual1"], b["residual1"])
    assert len(a["masks"]) == len(b["masks"]) == 3
    for s in range(3):
        assert a["masks"][s].dtype == bool
        assert a["masks"][s].shape == b["masks"][s].shape
        np.testing.assert_array_equal(a["masks"][s], b["masks"][s])
        # and something was sent, by every worker
        assert a["masks"][s].any(axis=1).all()
    assert new["dense"]["masks"] == [] and new["dense"]["residual1"] is None
