"""The comparison that decides `correct`.

What is compared is what the timed objects produced: the trainers that the
window drives (the arms of the mix's round) were taken through their first
three steps by the window's own call and feed (`harness.first_steps`), and
their readings are held against the plain reference (`reference/`), which
follows the same three steps in float32 from the benchmark's own weights and
the rows that were fed.

Numbers compared, each with a limit of its own (the configuration's
`limits`, set from chip readings; PERF.md section 2 has the readings):

  loss_gap          worst |loss - reference| / reference over the trainers'
                    three steps
  grad_norm_gap     the first gradient, rebuilt from the state after one step
                    (dense: momentum - wd*p0; sparse: that plus the workers'
                    mean residual, i.e. what was sent plus what was kept),
                    by the worst leaf: |norm - reference norm| over the
                    larger of the reference's norm of that leaf and of the
                    median leaf
  grad_rel_err      norm of (that gradient - the reference's) over the
                    reference's norm, all entries: catches a part misplaced
                    that leaves every norm alone
  delta_norm_gap    the parameters' change after the three steps, by the
                    worst leaf, the same way
  exact bookkeeping (limit 0 each): entries that arrived in the momentum
                    while every worker kept them (`double_counted`), large
                    entries zeroed in a residual that never arrived (`lost`),
                    states that are not of the type the configuration states
                    (`state_mismatches`), non-zero entries in the residual's
                    padding (`pad_nonzero`)
  sent_mantissa, residual_mantissa, momentum_mantissa
                    median distance of a value from its nearest bfloat16,
                    relative; about 1e-3 for float32 values, 0 for values
                    that went through a bfloat16 wire, residual or
                    accumulator. A floor, not a ceiling.
  sent_step1_over_k, selected_over_k
                    the cold first step sends at most k; once warm,
                    `num_selected` is inside [0.5, 2] k
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional

import numpy as np

from . import harness


# --------------------------------------------------------------- leaf norms

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


# ------------------------------------------------- the program's readings

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


# ------------------------------------------------ the reference's readings

class MemoryProbe:
    """What the reference holds on its chip, read where `follow_steps` says
    something is about to happen or has happened (its `probe`): the
    allocator's `bytes_in_use` before each gradient call, as each returns
    and as each step ends, and XLA's own account of the gradient program.
    The allocator counts arrays only, so the peak is the most that was in
    use before a gradient call plus that program's temporaries and fresh
    outputs, and never less than the most that was seen in use."""

    def __init__(self):
        import jax
        self.device = jax.local_devices()[0]
        self.start = self.in_use()
        self.arrays_peak = self.start
        self.before_call = 0
        self.program = {"temp": 0, "fresh_output": 0}

    def in_use(self) -> int:
        return int((self.device.memory_stats() or {}).get("bytes_in_use", 0))

    def __call__(self, event: str, info=None) -> None:
        if event == "grad_compiled":
            self.program = {
                "temp": max(self.program["temp"],
                            int(info.temp_size_in_bytes)),
                "fresh_output": max(
                    self.program["fresh_output"],
                    int(info.output_size_in_bytes
                        - info.alias_size_in_bytes))}
            return
        now = self.in_use()
        self.arrays_peak = max(self.arrays_peak, now)
        if event == "grad_call":
            self.before_call = max(self.before_call, now)

    def report(self) -> dict:
        need = self.program["temp"] + self.program["fresh_output"]
        return {"at_start_bytes": self.start,
                "arrays_peak_bytes": self.arrays_peak,
                "grad_call_temp_bytes": self.program["temp"],
                "grad_call_fresh_output_bytes": self.program["fresh_output"],
                "peak_bytes": max(self.arrays_peak, self.before_call + need)}


def reference_readings(config: dict, mix: dict, seed: int,
                       batches: Dict[str, list], masks: Optional[list],
                       weights: Dict[str, Any], precision: str = "float32",
                       probe=None) -> Dict[str, dict]:
    """Follow the trainers' first steps with the plain reference, one arm
    of `batches` after the other.
    `batches[arm][s]` is the global batch fed at step s (host arrays);
    `masks[s]` is bool [workers, n]: which entries each worker sent (read
    for the sparse arm only). Batches, masks and weights go to the
    reference as host arrays: it puts on its chip what one gradient call
    needs and nothing else (`reference/common.py` `follow_steps`)."""
    from .reference import common as C

    arms = list(batches)
    ref = harness.load_reference(config)
    tr = config["trainer"]
    nworkers = int(mix["nworkers"])
    per_worker = int(tr["batch_size"])
    steps = len(batches[arms[0]])
    warmup_steps = int(float(tr["warmup_epochs"]) * max(
        1, int(config["examples_per_worker"]) // per_worker))
    lrs = [C.lr_at(s, float(tr["lr"]), nworkers, warmup_steps)
           for s in range(steps)]

    def loss_fn(params, batch):
        return ref.loss(params, batch, config, precision)

    like = weights          # {path: array} in the program's order
    out = {}
    for arm in arms:
        shards = []
        for s in range(steps):
            x, y = batches[arm][s][:2]
            row = []
            for w in range(nworkers):
                sl = slice(w * per_worker, (w + 1) * per_worker)
                keep = None
                if "dropout" in config:
                    from .dropout import program_keep_mask
                    keep = np.asarray(program_keep_mask(
                        seed, s, w, (per_worker, config["dropout"]["width"]),
                        config["dropout"]["rate"]))
                row.append((np.asarray(x[sl]), np.asarray(y[sl]), keep))
            shards.append(row)
        # the reference lays parameters out by sorted path; masks come in
        # the program's order, which `order` maps onto it
        order = sorted(like)
        if arm == "sparse":
            step_masks = []
            for s in range(steps):
                per = []
                for w in range(nworkers):
                    parts = harness.split_flat(masks[s][w], like)
                    per.append(np.concatenate(
                        [parts[p].reshape(-1) for p in order]))
                step_masks.append(per)
        else:
            step_masks = [None] * steps
        r = C.follow_steps(loss_fn, {p: np.asarray(like[p]) for p in like},
                           shards, step_masks, lrs=lrs,
                           momentum=float(tr["momentum"]),
                           weight_decay=float(tr["weight_decay"]),
                           probe=probe)
        shapes = {p: like[p] for p in order}
        grad = _split_sorted(np.asarray(r["first_grad"]), shapes)
        params = _split_sorted(np.asarray(r["params"]), shapes)
        out[arm] = {"losses": r["losses"], "first_grad": grad,
                    "delta": {p: params[p] - np.asarray(like[p])
                              for p in order},
                    # each worker's own first gradient, flat in the
                    # program's order (the masks' order)
                    "first_grad_workers": [
                        np.concatenate([part[p].reshape(-1) for p in like])
                        for part in (_split_sorted(np.asarray(g), shapes)
                                     for g in r["first_grad_workers"])]}
    return out


def _split_sorted(flat: np.ndarray, shapes: Dict[str, Any]) -> dict:
    out, off = {}, 0
    for p in sorted(shapes):
        n = int(np.prod(shapes[p].shape))
        out[p] = flat[off:off + n].reshape(shapes[p].shape)
        off += n
    return out


# ------------------------------------------------------------ the verdict

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


# how each number is held to its limit: a ceiling ("max"), a floor
# ("min") or a band; the configuration's `limits` give the values
LIMIT_KINDS = {
    "loss_gap": "max", "loss_gap_first": "max", "head_grad_rel_err": "max",
    "grad_total_norm_gap": "max", "dense_delta_total_norm_gap": "max",
    "grad_norm_gap": "max", "grad_rel_err": "max",
    "delta_norm_gap": "max", "double_counted": "max", "lost": "max",
    "pad_nonzero": "max", "state_mismatches": "max",
    "sent_step1_over_k": "max", "sent_mantissa": "min",
    "residual_mantissa": "min", "momentum_mantissa": "min",
    "selected_over_k": "band", "compiles_in_window": "max",
    "failed_steps": "max", "residual_devices": "min",
}


def judge(numbers: Dict[str, Any], limits: Dict[str, Any]) -> tuple:
    """Hold each number against its limit. Returns (correct, lines): every
    number that has a limit is printed beside it. A number without a limit
    in the configuration's file is printed and not judged, except that a
    limit named in the file whose number is missing fails."""
    ok, lines = True, []
    for name, limit in limits.items():
        kind = LIMIT_KINDS.get(name, "max")
        if name not in numbers:
            ok = False
            lines.append(f"check {name}: MISSING (limit {limit})")
            continue
        v = numbers[name]
        if kind == "max":
            good = v <= limit
            rel = "<="
        elif kind == "min":
            good = v >= limit
            rel = ">="
        else:
            good = limit[0] <= v <= limit[1]
            rel = "in"
        ok = ok and bool(good)
        lines.append(f"check {name}: {v:.6g} {rel} {limit} "
                     f"{'ok' if good else 'FAILED'}")
    for name, v in numbers.items():
        if name not in limits:
            lines.append(f"check {name}: {v} (not judged)")
    return ok, lines


def as_record(numbers: Dict[str, Any], limits: Dict[str, Any]) -> dict:
    """Each number that has a limit beside it, for the result line."""
    def plain(v):
        if isinstance(v, (int, float)) and not np.isfinite(v):
            return str(v)
        return v
    return {name: {"value": plain(numbers.get(name)), "limit": limit}
            for name, limit in limits.items()}


def take_readings(arm, weights: Dict[str, np.ndarray], config: dict,
                  expected_states: Optional[dict] = None) -> tuple:
    """One arm's `program_readings`, with its batches and masks, TAKEN out
    of `arm.first` together with the full-length readings they were made
    from: whoever goes through the arms one after the other holds one
    arm's vectors at a time. Returns (readings, batches, masks), the first
    two keyed by the arm's name as `compare` and `reference_readings` take
    them."""
    f = arm.first
    mine = {arm.name: program_readings(arm, weights, config, expected_states)}
    batches, masks = {arm.name: f.pop("batches")}, f.pop("masks")
    for key in ("momentum1", "residual1", "params"):
        f.pop(key)
    return mine, batches, masks


def worst(per_arm: list) -> Dict[str, Any]:
    """`compare`'s numbers of one arm at a time, merged: each number is a
    worst case over the arms, and a `_leaf` goes with its number."""
    out: Dict[str, Any] = {}
    for numbers in per_arm:
        for key, v in numbers.items():
            if key.endswith("_leaf"):
                continue
            if key not in out or v > out[key]:
                out[key] = v
                if key + "_leaf" in numbers:
                    out[key + "_leaf"] = numbers[key + "_leaf"]
    return out


def run_check(cell: dict, seed: int, arms_first: Dict[str, dict],
              weights_host: Dict[str, np.ndarray], window: dict,
              expected_states: Optional[dict] = None,
              precision: str = "float32",
              memory: Optional[dict] = None) -> tuple:
    """The whole comparison after the window has closed and the trainers
    are freed, over the arms that ran. `arms_first[arm]` is a stand-in for
    `Arm` with `.name` and `.first`.

    One arm after the other, and it CONSUMES what it reads: an arm's
    full-length readings (`momentum1`, `residual1`, `params`, `masks`,
    `batches`) are taken out of its `.first` as soon as they are read, and
    its gradients and parameters are dropped once they are compared, so
    that at a configuration of hundreds of millions of parameters the host
    holds one arm's vectors at a time (PERF.md section 4). What the
    reference held on its chip goes into `memory["check"]` where a dict is
    given. Returns (correct, numbers, lines, seconds)."""
    t0 = time.perf_counter()
    config, mix = cell["config_data"], cell["mix"]
    probe = MemoryProbe()
    per_arm, sparse_only = [], {}
    for name, arm in arms_first.items():
        mine, batches, masks = take_readings(arm, weights_host, config,
                                             expected_states)
        ref = reference_readings(config, mix, seed, batches, masks,
                                 weights_host, precision, probe=probe)
        del batches, masks
        per_arm.append(compare(mine, ref, config.get("head_leaf")))
        if name == "sparse":
            sparse_only = dict(mine[name]["exact"])
            sparse_only["lost"] = lost_entries(mine[name], ref[name],
                                               int(arm.first["k"]))
        del mine, ref
    if memory is not None:
        memory["check"] = probe.report()
    numbers = worst(per_arm)
    numbers.update(sparse_only)
    numbers["compiles_in_window"] = int(window.get("compiles_in_window", 0))
    numbers["failed_steps"] = int(window.get("failed_steps", 0))
    ok, lines = judge(numbers, config["limits"])
    return ok, numbers, lines, time.perf_counter() - t0
