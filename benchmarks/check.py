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

def leaf_sums(mine: Dict[str, np.ndarray],
              ref: Dict[str, np.ndarray]) -> Dict[str, tuple]:
    """Per leaf of `ref`: (sum of ref^2, sum of mine^2, sum of (mine -
    ref)^2), each in float64, from ONE pass over both, a block at a time:
    every norm and error the comparison reads is made of these. (A block's
    sums are float64 dot products, added up in the blocks' order: the last
    bits may differ from a whole-leaf sum's, nothing else.)"""
    flat = {p: (np.asarray(mine[p]).reshape(-1),
                np.asarray(ref[p]).reshape(-1)) for p in ref}
    tasks = [(p, b) for p in ref for b in harness.blocks(flat[p][1].size)]

    def sums(task):
        p, b = task
        a = flat[p][0][b].astype(np.float64)
        r = flat[p][1][b].astype(np.float64)
        d = a - r
        return float(np.dot(r, r)), float(np.dot(a, a)), float(np.dot(d, d))

    out = {p: (0.0, 0.0, 0.0) for p in ref}
    for (p, _), got in zip(tasks, harness.on_blocks(sums, tasks)):
        out[p] = tuple(x + y for x, y in zip(out[p], got))
    return out


def leaf_norm_gap(sums: Dict[str, tuple]) -> tuple:
    """Worst leaf: the gap between the two norms (not the norm of the
    difference) over the larger of the reference's norm of that leaf and of
    its median leaf. Returns (gap, leaf)."""
    rn = {p: float(np.sqrt(v[0])) for p, v in sums.items()}
    mn = {p: float(np.sqrt(v[1])) for p, v in sums.items()}
    median = float(np.median(list(rn.values())))
    worst, where = 0.0, ""
    for p in sums:
        den = max(rn[p], median)
        gap = abs(mn[p] - rn[p])
        gap = (gap / den) if den > 0 else (0.0 if gap == 0 else float("inf"))
        if gap > worst:
            worst, where = gap, p
    return worst, where


def leaf_table(sums: Dict[str, tuple]) -> dict:
    """Per leaf: (reference norm, this norm, norm of the difference). What
    a limit's choice of number is read from."""
    return {p: [float(np.sqrt(x)) for x in v] for p, v in sums.items()}


def rel_err(sums: Dict[str, tuple]) -> float:
    """Norm of the difference over the reference's norm, over every leaf
    of `sums`."""
    num = sum(v[2] for v in sums.values())
    den = sum(v[0] for v in sums.values())
    return float(np.sqrt(num / den)) if den > 0 else float("inf")


def total_norm_gap(sums: Dict[str, tuple]) -> float:
    """Gap between the two whole-vector norms over the reference's."""
    r = float(np.sqrt(sum(v[0] for v in sums.values())))
    m = float(np.sqrt(sum(v[1] for v in sums.values())))
    return abs(m - r) / r if r > 0 else float("inf")


def mantissa_distance(values, sample: int = 1 << 20) -> float:
    """Median relative distance of non-zero float32 values from their
    nearest bfloat16: of all of them, or of every (count // sample)-th in
    the order they stand in (a 2-D array's row by row). The non-zero values
    are counted first and only the sampled ones are gathered, a block at a
    time, so that no full-length copy is made."""
    import ml_dtypes
    values = np.asarray(values, np.float32)
    rows = list(values) if values.ndim == 2 else [values.reshape(-1)]
    tasks = [(i, b) for i, row in enumerate(rows)
             for b in harness.blocks(row.size)]
    counts = harness.on_blocks(
        lambda t: int(np.count_nonzero(rows[t[0]][t[1]])), tasks)
    total = sum(counts)
    if total == 0:
        return 0.0
    step = total // sample if total > sample else 1
    before = np.concatenate([[0], np.cumsum(counts)[:-1]])

    def pick(j):
        (i, b), first = tasks[j], int(-before[j] % step)
        if first >= counts[j]:
            return np.empty((0,), np.float32)
        block = rows[i][b]
        return block[block != 0][first::step]

    v = np.concatenate(harness.on_blocks(pick, range(len(tasks))))
    r = v.astype(ml_dtypes.bfloat16).astype(np.float32)
    return float(np.median(np.abs(v - r) / np.abs(v)))


# ------------------------------------------------- the program's readings

def _pieces(like: Dict[str, Any]) -> list:
    """(path, where in the flat vector of `like`'s order, where in the
    leaf's own flat view) for every block of every leaf."""
    out, off = [], 0
    for p, leaf in like.items():
        size = int(np.prod(leaf.shape))
        out += [(p, slice(off + b.start, off + b.stop), b)
                for b in harness.blocks(size)]
        off += size
    return out


def program_readings(arm, weights: Dict[str, np.ndarray], config: dict,
                     expected_states: Optional[dict] = None) -> dict:
    """What the comparison reads of one trainer, from `arm.first`. The
    full-length arithmetic is elementwise float32, a block of a leaf at a
    time on threads: what it keeps of full length is what it returns (the
    first gradient, the parameters' change and, of the sparse trainer, the
    two masks)."""
    f = arm.first
    wd = np.float32(config["trainer"]["weight_decay"])
    like = f["params"]
    n = sum(int(v.size) for v in like.values())
    m1 = f["momentum1"][:n]
    sparse = arm.name == "sparse"
    res1 = f["residual1"] if sparse else None
    nworkers = res1.shape[0] if sparse else 0
    grad = np.empty((n,), np.float32)
    delta = np.empty((n,), np.float32)
    sent_any = np.empty((n,), bool) if sparse else None
    zeroed = np.empty((nworkers, n), bool) if sparse else None

    def piece(task):
        p, at, b = task
        p0 = weights[p].reshape(-1)[b]
        wd_p0 = wd * p0
        arrived = m1[at] - wd_p0
        np.subtract(np.asarray(f["params"][p]).reshape(-1)[b], p0,
                    out=delta[at])
        if not sparse:
            grad[at] = arrived
            return None
        grad[at] = arrived + res1[:, at].mean(axis=0, dtype=np.float32)
        # exact bookkeeping, from the system's own state after one sparse
        # step
        sent = m1[at] != wd_p0
        zero = res1[:, at] == 0
        sent_any[at], zeroed[:, at] = sent, zero
        return (int(np.count_nonzero(sent & ~zero.any(axis=0))),
                int(np.count_nonzero(sent)), arrived[sent])

    got = harness.on_blocks(piece, _pieces(like))
    out: Dict[str, Any] = {"losses": f["losses"],
                           "first_grad": harness.split_flat(grad, like),
                           "delta": harness.split_flat(delta, like)}
    if not sparse:
        return out
    k = int(f["k"])
    exact = {
        "double_counted": sum(g[0] for g in got),
        "pad_nonzero": int(np.count_nonzero(res1[:, n:])),
        "sent_mantissa": mantissa_distance(
            np.concatenate([g[2] for g in got])),
        "residual_mantissa": mantissa_distance(res1[:, :n]),
        "momentum_mantissa": mantissa_distance(m1),
        "sent_step1_over_k": float(sum(g[1] for g in got)) / (k * nworkers),
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
                       probe=None, parts=None) -> Dict[str, dict]:
    """Follow the trainers' first steps with the plain reference, one arm
    of `batches` after the other.
    `batches[arm][s]` is the global batch fed at step s (host arrays);
    `masks[s]` is bool [workers, n]: which entries each worker sent (read
    for the sparse arm only). Batches, masks and weights go to the
    reference as host arrays: it puts on its chip what one gradient call
    needs and nothing else (`reference/common.py` `follow_steps`)."""
    from .reference import common as C

    parts = parts if parts is not None else harness.Parts()
    arms = list(batches)
    with parts("rows and masks to the reference's order"):
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
        with parts("rows and masks to the reference's order"):
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
                            seed, s, w,
                            (per_worker, config["dropout"]["width"]),
                            config["dropout"]["rate"]))
                    row.append((np.asarray(x[sl]), np.asarray(y[sl]), keep))
                shards.append(row)
            # the reference lays parameters out by sorted path; masks come
            # in the program's order
            order = sorted(like)
            step_masks = [
                [_reordered(masks[s][w], like, list(like), order)
                 for w in range(nworkers)] if arm == "sparse" else None
                for s in range(steps)]
        r = C.follow_steps(loss_fn, {p: np.asarray(like[p]) for p in like},
                           shards, step_masks, lrs=lrs,
                           momentum=float(tr["momentum"]),
                           weight_decay=float(tr["weight_decay"]),
                           probe=probe, clock=parts)
        with parts("gradients and parameters to the program's order"):
            shapes = {p: like[p] for p in order}
            delta = np.empty_like(r["params"])
            params = _split_sorted(r["params"], shapes)

            def change(task):
                p, at, b = task
                np.subtract(params[p].reshape(-1)[b],
                            np.asarray(like[p]).reshape(-1)[b], out=delta[at])

            harness.on_blocks(change, _pieces(shapes))
            out[arm] = {"losses": r["losses"],
                        "first_grad": _split_sorted(r["first_grad"], shapes),
                        "delta": _split_sorted(delta, shapes),
                        # each worker's own first gradient, flat in the
                        # program's order (the masks' order)
                        "first_grad_workers": [
                            _reordered(g, like, order, list(like))
                            for g in r["first_grad_workers"]]}
    return out


def _reordered(flat: np.ndarray, like: Dict[str, Any], src: list,
               dst: list) -> np.ndarray:
    """A flat vector that holds `like`'s leaves in the order `src`, in the
    order `dst`: itself where the two orders are one, else one pass."""
    if list(src) == list(dst):
        return flat
    size = {p: int(np.prod(like[p].shape)) for p in like}
    at, off = {}, 0
    for p in src:
        at[p], off = off, off + size[p]
    out, off = np.empty_like(flat), 0
    for p in dst:
        out[off:off + size[p]] = flat[at[p]:at[p] + size[p]]
        off += size[p]
    return out


def _split_sorted(flat: np.ndarray, shapes: Dict[str, Any]) -> dict:
    out, off = {}, 0
    for p in sorted(shapes):
        n = int(np.prod(shapes[p].shape))
        out[p] = flat[off:off + n].reshape(shapes[p].shape)
        off += n
    return out


# ------------------------------------------------------------ the verdict

def compare(mine: Dict[str, dict], ref: Dict[str, dict],
            head_leaf: Optional[str] = None,
            table: Optional[dict] = None) -> Dict[str, Any]:
    """The numbers compared, from two sets of readings ({arm: readings}).
    `head_leaf` names the parameter nearest the loss (the configuration's
    `head_leaf`): its first gradient goes through the forward pass only, so
    its relative error is steady from seed to seed and is what a lower
    precision moves most against its own spread. Where `table` is a dict
    it is given `leaf_table` of each arm's two quantities."""
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
        grad = leaf_sums(mine[arm]["first_grad"], ref[arm]["first_grad"])
        delta = leaf_sums(mine[arm]["delta"], ref[arm]["delta"])
        if table is not None:
            table[f"{arm}.first_grad"] = leaf_table(grad)
            table[f"{arm}.delta"] = leaf_table(delta)
        if head_leaf:
            head_err = max(head_err, rel_err({head_leaf: grad[head_leaf]}))
        g_total = max(g_total, total_norm_gap(grad))
        if arm == "dense":
            d_total = total_norm_gap(delta)
        g, d = leaf_norm_gap(grad), leaf_norm_gap(delta)
        g_gap = max(g_gap, (g[0], f"{arm}:{g[1]}"))
        d_gap = max(d_gap, (d[0], f"{arm}:{d[1]}"))
        g_err = max(g_err, rel_err(grad))
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
    sent, and never arrived in the momentum. The 2k-th largest magnitude
    is an order statistic, so it is the same number whether one partition
    finds it in the whole vector or, as here, in the 2k largest of each of
    a few long stretches, which the threads take one each."""
    lost = 0
    for w, g in enumerate(ref_sparse["first_grad_workers"]):
        top = min(2 * k, g.size - 1)
        stretches = max(1, min(harness.THREADS, g.size // (8 * max(top, 1))))
        edges = np.linspace(0, g.size, stretches + 1).astype(np.int64)

        def largest(i):
            a = np.abs(g[edges[i]:edges[i + 1]])
            return np.partition(a, a.size - top)[a.size - top:]

        near = (np.concatenate(harness.on_blocks(largest, range(stretches)))
                if stretches > 1 else np.abs(g))
        thr = np.partition(near, near.size - top)[near.size - top]
        zeroed, sent = mine_sparse["zeroed1"][w], mine_sparse["sent_any1"]

        def count(b):
            a = np.abs(g[b])
            return int(np.count_nonzero(
                zeroed[b] & ~sent[b] & (a >= thr) & (a > 0)))

        lost += sum(harness.on_blocks(count, harness.blocks(g.size)))
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
              memory: Optional[dict] = None, parts=None) -> tuple:
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
    given, and the seconds of each part into `parts` (`harness.Parts`:
    the run's `check by part` line). Returns (correct, numbers, lines,
    seconds)."""
    t0 = time.perf_counter()
    parts = parts if parts is not None else harness.Parts()
    config, mix = cell["config_data"], cell["mix"]
    with parts("the program's readings"):
        probe = MemoryProbe()
    per_arm, sparse_only = [], {}
    for name, arm in arms_first.items():
        with parts("the program's readings"):
            mine, batches, masks = take_readings(arm, weights_host, config,
                                                 expected_states)
        ref = reference_readings(config, mix, seed, batches, masks,
                                 weights_host, precision, probe=probe,
                                 parts=parts)
        with parts("compare"):
            del batches, masks
            per_arm.append(compare(mine, ref, config.get("head_leaf")))
        if name == "sparse":
            with parts("lost_entries"):
                sparse_only = dict(mine[name]["exact"])
                sparse_only["lost"] = lost_entries(mine[name], ref[name],
                                                   int(arm.first["k"]))
        with parts("readings freed"):
            del mine, ref
    with parts("judge"):
        if memory is not None:
            memory["check"] = probe.report()
        numbers = worst(per_arm)
        numbers.update(sparse_only)
        numbers["compiles_in_window"] = int(
            window.get("compiles_in_window", 0))
        numbers["failed_steps"] = int(window.get("failed_steps", 0))
        ok, lines = judge(numbers, config["limits"])
    return ok, numbers, lines, time.perf_counter() - t0
