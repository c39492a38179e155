"""`check.py`'s numbers as they were made up to PR 26, word for word: whole
leaves converted to float64 for every norm, whole-vector numpy on one thread
for the program's readings, one full-length partition a worker in
`lost_entries`. `test_check_numbers.py` holds the functions that replaced
them against these."""

from typing import Any, Dict, Optional

import numpy as np

from benchmarks import harness


def leaf_norm_gap(mine: Dict[str, np.ndarray],
                  ref: Dict[str, np.ndarray]) -> tuple:
    """Worst leaf: the gap between the two norms (not the norm of the
    difference) over the larger of the reference's norm of that leaf and of
    its median leaf. Returns (gap, leaf)."""
    rn = {p: float(np.linalg.norm(ref[p].astype(np.float64))) for p in ref}
    mn = {p: float(np.linalg.norm(mine[p].astype(np.float64))) for p in ref}
    median = float(np.median(list(rn.values())))
    worst, where = 0.0, ""
    for p in ref:
        den = max(rn[p], median)
        gap = abs(mn[p] - rn[p])
        gap = (gap / den) if den > 0 else (0.0 if gap == 0 else float("inf"))
        if gap > worst:
            worst, where = gap, p
    return worst, where


def leaf_table(mine: Dict[str, dict], ref: Dict[str, dict]) -> dict:
    """Per arm, quantity and leaf: (reference norm, this norm, norm of the
    difference). What a limit's choice of number is read from."""
    out = {}
    for arm in ref:
        for q in ("first_grad", "delta"):
            out[f"{arm}.{q}"] = {
                p: [float(np.linalg.norm(ref[arm][q][p].astype(np.float64))),
                    float(np.linalg.norm(mine[arm][q][p].astype(np.float64))),
                    float(np.linalg.norm(
                        mine[arm][q][p].astype(np.float64)
                        - ref[arm][q][p].astype(np.float64)))]
                for p in ref[arm][q]}
    return out


def rel_err(mine: Dict[str, np.ndarray], ref: Dict[str, np.ndarray]) -> float:
    num = sum(float(np.sum(np.square(mine[p].astype(np.float64)
                                     - ref[p].astype(np.float64))))
              for p in ref)
    den = sum(float(np.sum(np.square(ref[p].astype(np.float64))))
              for p in ref)
    return float(np.sqrt(num / den)) if den > 0 else float("inf")


def mantissa_distance(values: np.ndarray, sample: int = 1 << 20) -> float:
    """Median relative distance of non-zero float32 values from their
    nearest bfloat16."""
    import ml_dtypes
    v = np.asarray(values, np.float32).reshape(-1)
    v = v[v != 0]
    if v.size == 0:
        return 0.0
    if v.size > sample:
        v = v[:: v.size // sample]
    r = v.astype(ml_dtypes.bfloat16).astype(np.float32)
    return float(np.median(np.abs(v - r) / np.abs(v)))


def program_readings(arm, weights: Dict[str, np.ndarray], config: dict,
                     expected_states: Optional[dict] = None) -> dict:
    """What the comparison reads of one trainer, from `arm.first`."""
    f = arm.first
    tr_cfg = config["trainer"]
    wd = np.float32(tr_cfg["weight_decay"])
    like = f["params"]
    n = sum(int(v.size) for v in like.values())
    p0 = np.concatenate([weights[p].reshape(-1) for p in like])
    wd_p0 = wd * p0
    m1 = f["momentum1"][:n]
    arrived = m1 - wd_p0
    out: Dict[str, Any] = {"losses": f["losses"]}
    if arm.name == "dense":
        grad = arrived
    else:
        res1 = f["residual1"]
        nworkers = res1.shape[0]
        grad = arrived + res1[:, :n].mean(axis=0, dtype=np.float32)
    out["first_grad"] = harness.split_flat(grad, like)
    out["delta"] = {p: f["params"][p] - weights[p] for p in like}
    if arm.name != "sparse":
        return out

    # exact bookkeeping, from the system's own state after one sparse step
    sent_any = m1 != wd_p0
    zeroed = res1[:, :n] == 0
    kept_all = ~zeroed.any(axis=0)
    k = int(f["k"])
    exact = {
        "double_counted": int(np.count_nonzero(sent_any & kept_all)),
        "pad_nonzero": int(np.count_nonzero(res1[:, n:])),
        "sent_mantissa": mantissa_distance(arrived[sent_any]),
        "residual_mantissa": mantissa_distance(res1[:, :n]),
        "momentum_mantissa": mantissa_distance(m1),
        "sent_step1_over_k": float(np.count_nonzero(sent_any)) / (
            k * nworkers),
        "selected_over_k": float(f["warm_selected"]) / k,
    }
    states = dict(f["dtypes"])
    states.update(f["built"])
    want = expected_states if expected_states is not None else config["states"]
    exact["state_mismatches"] = sum(
        1 for key, v in want.items() if key in states and states[key] != v)
    exact["residual_devices"] = f["residual_devices"]
    out["exact"] = exact
    out["zeroed1"] = zeroed
    out["sent_any1"] = sent_any
    return out


def total_norm_gap(mine: Dict[str, np.ndarray],
                   ref: Dict[str, np.ndarray]) -> float:
    """Gap between the two whole-vector norms over the reference's."""
    def norm(tree):
        return float(np.sqrt(sum(float(np.sum(np.square(
            v.astype(np.float64)))) for v in tree.values())))
    r = norm(ref)
    return abs(norm(mine) - r) / r if r > 0 else float("inf")


def compare(mine: Dict[str, dict], ref: Dict[str, dict],
            head_leaf: Optional[str] = None) -> Dict[str, Any]:
    """The numbers compared, from two sets of readings ({arm: readings}).
    `head_leaf` names the parameter nearest the loss (the configuration's
    `head_leaf`): its first gradient goes through the forward pass only, so
    its relative error is steady from seed to seed and is what a lower
    precision moves most against its own spread."""
    numbers: Dict[str, Any] = {}
    loss_gap, g_gap, d_gap, g_err = 0.0, (0.0, ""), (0.0, ""), 0.0
    first_gap = head_err = g_total = d_total = 0.0
    for arm in ref:
        for i, (a, b) in enumerate(zip(mine[arm]["losses"],
                                       ref[arm]["losses"])):
            gap = abs(a - b) / abs(b) if np.isfinite(a) else float("inf")
            loss_gap = max(loss_gap, gap)
            if i == 0:
                first_gap = max(first_gap, gap)
        if head_leaf:
            head_err = max(head_err, rel_err(
                {head_leaf: mine[arm]["first_grad"][head_leaf]},
                {head_leaf: ref[arm]["first_grad"][head_leaf]}))
        g_total = max(g_total, total_norm_gap(mine[arm]["first_grad"],
                                              ref[arm]["first_grad"]))
        if arm == "dense":
            d_total = total_norm_gap(mine[arm]["delta"], ref[arm]["delta"])
        g = leaf_norm_gap(mine[arm]["first_grad"], ref[arm]["first_grad"])
        d = leaf_norm_gap(mine[arm]["delta"], ref[arm]["delta"])
        g_gap = max(g_gap, (g[0], f"{arm}:{g[1]}"))
        d_gap = max(d_gap, (d[0], f"{arm}:{d[1]}"))
        g_err = max(g_err, rel_err(mine[arm]["first_grad"],
                                   ref[arm]["first_grad"]))
    numbers["loss_gap_first"] = first_gap
    numbers["loss_gap"] = loss_gap
    if head_leaf:
        numbers["head_grad_rel_err"] = head_err
    numbers["grad_total_norm_gap"] = g_total
    if "dense" in ref:
        numbers["dense_delta_total_norm_gap"] = d_total
    numbers["grad_norm_gap"] = g_gap[0]
    numbers["grad_norm_gap_leaf"] = g_gap[1]
    numbers["grad_rel_err"] = g_err
    numbers["delta_norm_gap"] = d_gap[0]
    numbers["delta_norm_gap_leaf"] = d_gap[1]
    return numbers


def lost_entries(mine_sparse: dict, ref_sparse: dict, k: int) -> int:
    """Entries among the 2k largest of a worker's own reference gradient
    that are zero in that worker's residual after the first step, so were
    sent, and never arrived in the momentum."""
    lost = 0
    for w, g in enumerate(ref_sparse["first_grad_workers"]):
        g = np.abs(g)
        top = min(2 * k, g.size - 1)
        thr = np.partition(g, g.size - top)[g.size - top]
        lost += int(np.count_nonzero(
            mine_sparse["zeroed1"][w] & ~mine_sparse["sent_any1"]
            & (g >= thr) & (g > 0)))
    return lost
