"""Adaptive-policy A/B harness — adaptive engine vs every fixed policy
over the 5-config BASELINE matrix (ISSUE 6 acceptance artifact).

Three modes, one acceptance contract:

* **replay** (default) — deterministic closed-loop replay of the REAL
  :class:`~gaussiank_sgd_tpu.policy.engine.PolicyEngine` (same rules,
  hysteresis, cooldown, budget, probation) over MEASURED per-arm step
  times from a committed bench matrix artifact
  (analysis/artifacts/bench_matrix_r5.json by default — per-selector
  ``sparse_ms``/``dense_ms`` cells priced by analysis/bench_matrix.py's
  paired-round protocol). Each simulated log interval feeds the engine a
  schema-shaped ``train`` record whose ``step_s`` is the measured time of
  the arm currently bound; decisions switch the arm and charge an
  explicit recompile penalty. No wall-clock enters the loop — the replay
  is bit-reproducible, so the committed artifact can be re-derived from
  the committed matrix.
* **--measure** — price the per-arm matrix live with benchlib first
  (perf platforms; same cells, fresh numbers), then replay over them.
* **--smoke** — CI arm: two LIVE mnistnet Trainer runs (``--policy
  static`` vs ``--policy adaptive``, same seed) on the virtual 8-device
  mesh; asserts the adaptive run completes, its event stream passes
  STRICT schema validation (policy events included), engine recompiles
  respect the budget, and adaptive throughput does not lose to static
  beyond a CI-noise tolerance. Exits non-zero on any violation.

Scoring (the acceptance metric): per config and per policy, the
**median interval step-throughput ratio** ``dense_ms / interval_ms`` —
for a fixed policy every interval runs its one arm; for the adaptive
policy the intervals follow the engine's decisions, so exploration and
recompile penalties land in the minority intervals and the median shows
the arm the engine *converged to*. The mean ratio (where exploration
dilution does show) is reported next to it. Acceptance:
``min-over-configs`` (worst config) of the adaptive median must be >=
the best fixed policy's worst-config median (minimax >= maximin: the
adaptive engine may not lose the binding number to ANY single fixed
choice), and the adaptive policy must be strictly better than at least
one fixed policy on at least one config. The harness itself enforces
this and exits non-zero otherwise.

Artifact: analysis/artifacts/policy_ab_<tag>.json — per-config
per-policy medians/means, the engine's full decision log, recompile
counts, and the acceptance block.

Run: python analysis/policy_ab.py [--matrix PATH] [--horizon 120]
     [--smoke] [--measure]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

ARTIFACTS = os.path.join(REPO, "analysis", "artifacts")
DEFAULT_MATRIX = os.path.join(ARTIFACTS, "bench_matrix_r5.json")

# simulated boundary cadence: one decision tick per log interval, the
# trainer's recompile-safe boundary contract (docs/ADAPTIVE.md)
STEPS_PER_INTERVAL = 10
# dense warm-up intervals fed before the sparse phase — gives the engine
# the measured dense reference the SelectorRule overhead gate needs,
# exactly like the trainer's compress_warmup_steps phase does live
WARMUP_INTERVALS = 3
# one program rebuild priced in dense-step equivalents; explicit in the
# artifact so the charge is auditable (a jit rebuild of a 5-60M-param
# step is tens of step-times, not free, not catastrophic)
RECOMPILE_PENALTY_STEPS = 50


def _load_matrix(path: str, density: float = 0.001):
    """-> [{key, dense_ms, arms: {name: sparse_ms}, platform}] per config."""
    with open(path) as f:
        entries = json.load(f)
    configs = []
    for e in entries:
        cells = [c for c in e["cells"] if c.get("density") == density]
        if not cells:
            continue
        configs.append({
            "key": e["config"],
            "model": e.get("model"),
            "platform": e.get("platform"),
            "dense_ms": float(cells[0]["dense_ms"]),
            "arms": {c["compressor"]: float(c["sparse_ms"]) for c in cells},
        })
    if not configs:
        raise ValueError(f"no density={density} cells in {path}")
    return configs


def _floor_proxy_ms(cfg) -> float:
    """Per-config exploration budget when no same-platform roofline
    artifact applies: the best MEASURED arm's overhead (clamped to a
    small positive floor — a negative overhead means sparse beat dense,
    where exploration has nothing to buy)."""
    best = min(t - cfg["dense_ms"] for t in cfg["arms"].values())
    return max(best, 0.02 * cfg["dense_ms"])


def _replay_adaptive(cfg, horizon: int, start_arm: str):
    """Run the real engine over measured arm times for one config.
    Returns (interval_ms list, decision events, recompiles, final arm).
    """
    from gaussiank_sgd_tpu.policy import PolicyEngine, SelectorRule
    from gaussiank_sgd_tpu.policy.rules import KNOB_COMPRESSOR

    decisions = []
    engine = PolicyEngine(
        [SelectorRule(list(cfg["arms"]))],
        publish=lambda ev, payload: decisions.append(
            dict(payload, event=ev, config=cfg["key"])),
        knobs={KNOB_COMPRESSOR: start_arm},
        floor_ms=_floor_proxy_ms(cfg))

    dense_s = cfg["dense_ms"] / 1e3
    step = 0
    for _ in range(WARMUP_INTERVALS):
        step += STEPS_PER_INTERVAL
        # dense warm-up record: no wire_format field -> DENSE_ARM
        engine.emit({"event": "train", "step": step, "loss": 1.0,
                     "step_s": dense_s})

    arm = start_arm
    interval_ms = []
    for _ in range(horizon):
        step += STEPS_PER_INTERVAL
        arm_s = cfg["arms"][arm] / 1e3
        engine.emit({"event": "train", "step": step, "loss": 1.0,
                     "step_s": arm_s, "wire_format": "u16bf16",
                     "bytes_sent": 0.0})
        ms = cfg["arms"][arm]
        # boundary tick, trainer ordering: revert check first, then decide
        revert = engine.check_revert(rollback_pending=False)
        if revert is not None:           # never fires here (loss constant)
            arm = revert.new
            ms += RECOMPILE_PENALTY_STEPS * cfg["dense_ms"] \
                / STEPS_PER_INTERVAL
            engine.note_reverted(revert)
        else:
            d = engine.decide(rollback_pending=False)
            if d is not None and d.knob == KNOB_COMPRESSOR:
                arm = d.new
                ms += RECOMPILE_PENALTY_STEPS * cfg["dense_ms"] \
                    / STEPS_PER_INTERVAL
                engine.note_applied(d)
        interval_ms.append(ms)
    return interval_ms, decisions, engine.recompiles, arm


def run_replay(matrix_path: str, horizon: int):
    from gaussiank_sgd_tpu.compressors import DEFAULT_SELECTOR

    configs = _load_matrix(matrix_path)
    fixed_policies = sorted({a for c in configs for a in c["arms"]})
    per_config = {}
    all_decisions = []
    total_recompiles = 0
    for cfg in configs:
        start = DEFAULT_SELECTOR if DEFAULT_SELECTOR in cfg["arms"] \
            else sorted(cfg["arms"])[0]
        ims, decisions, recompiles, final_arm = \
            _replay_adaptive(cfg, horizon, start)
        all_decisions.extend(decisions)
        total_recompiles += recompiles
        dense = cfg["dense_ms"]
        row = {
            "dense_ms": dense,
            "adaptive": {
                "ratio_median": round(dense / statistics.median(ims), 4),
                "ratio_mean": round(dense * len(ims) / sum(ims), 4),
                "recompiles": recompiles,
                "start_arm": start,
                "final_arm": final_arm,
            },
            "fixed": {},
        }
        for arm in fixed_policies:
            if arm not in cfg["arms"]:
                continue
            r = round(dense / cfg["arms"][arm], 4)
            row["fixed"][arm] = {"ratio_median": r, "ratio_mean": r}
        per_config[cfg["key"]] = row
    return {
        "configs": per_config,
        "fixed_policies": fixed_policies,
        "decision_log": all_decisions,
        "recompiles_total": total_recompiles,
        "horizon_intervals": horizon,
        "steps_per_interval": STEPS_PER_INTERVAL,
        "recompile_penalty_steps": RECOMPILE_PENALTY_STEPS,
        "matrix_source": os.path.relpath(matrix_path, REPO),
        "matrix_platform": configs[0].get("platform"),
    }


def evaluate(result) -> dict:
    """The acceptance block: minimax >= maximin + a strict win."""
    cfgs = result["configs"]
    adaptive_worst_key, adaptive_worst = min(
        ((k, row["adaptive"]["ratio_median"]) for k, row in cfgs.items()),
        key=lambda kv: kv[1])
    fixed_worst = {}
    for p in result["fixed_policies"]:
        vals = [row["fixed"][p]["ratio_median"] for row in cfgs.values()
                if p in row["fixed"]]
        fixed_worst[p] = min(vals)
    best_fixed, best_fixed_worst = max(fixed_worst.items(),
                                       key=lambda kv: kv[1])
    strict_wins = [
        {"config": k, "fixed_policy": p,
         "adaptive": row["adaptive"]["ratio_median"],
         "fixed": row["fixed"][p]["ratio_median"]}
        for k, row in cfgs.items() for p in row["fixed"]
        if row["adaptive"]["ratio_median"]
        > row["fixed"][p]["ratio_median"] + 1e-9]
    return {
        "adaptive_worst_config": adaptive_worst_key,
        "adaptive_worst_ratio_median": adaptive_worst,
        "fixed_worst_ratio_median": fixed_worst,
        "best_fixed_policy": best_fixed,
        "best_fixed_worst_ratio_median": best_fixed_worst,
        "minimax_ok": adaptive_worst >= best_fixed_worst,
        "n_strict_wins": len(strict_wins),
        "strict_wins_sample": strict_wins[:5],
        "ok": (adaptive_worst >= best_fixed_worst
               and len(strict_wins) > 0),
    }


# -- live measurement (perf platforms) -------------------------------------

def measure_matrix(horizon_steps: int = 10, rounds: int = 2):
    """Price the per-arm matrix live with benchlib (bench.py CONFIGS,
    full sweep on every config), shaped like _load_matrix output."""
    from bench import CONFIGS, SWEEP
    from gaussiank_sgd_tpu.benchlib import bench_model
    import jax

    platform = jax.devices()[0].platform
    configs = []
    for key, model, dataset, batch, n_steps, _ in CONFIGS:
        times = bench_model(model, dataset, batch, 0.001, SWEEP,
                            n_steps=min(n_steps, horizon_steps),
                            rounds=rounds)
        configs.append({
            "key": key, "model": model, "platform": platform,
            "dense_ms": 1e3 * times["dense"],
            "arms": {c: 1e3 * times[c] for c in SWEEP},
        })
    return configs


# -- smoke (CI): live adaptive vs static mnistnet Trainer ------------------

SMOKE_TOLERANCE = 0.70   # adaptive examples/s >= 0.70x static (CI noise)


def run_smoke(tmp_dir: str) -> dict:
    """Two live runs, same seed: --policy static vs --policy adaptive.
    The adaptive engine makes no decision on mnistnet (no roofline floor,
    no regret record), so this arm prices the CLOSED-LOOP OVERHEAD and
    validates the event plumbing, not the retuning."""
    from gaussiank_sgd_tpu.telemetry.events import validate_file
    from gaussiank_sgd_tpu.training.config import TrainConfig
    from gaussiank_sgd_tpu.training.trainer import Trainer

    def cfg(policy):
        return TrainConfig(
            dnn="mnistnet", dataset="mnist", batch_size=8, nworkers=8,
            lr=0.05, momentum=0.9, weight_decay=0.0, epochs=1,
            max_steps=40, compressor="gaussian", density=0.01,
            compress_warmup_steps=4, warmup_epochs=0.0,
            compute_dtype="float32", log_every=5, eval_every_epochs=0,
            save_every_epochs=0, seed=0, policy=policy,
            output_dir=os.path.join(tmp_dir, policy), run_id=policy)

    def median_step_s(run_dir):
        recs = [json.loads(line) for line in
                open(os.path.join(run_dir, "metrics.jsonl"))]
        ss = [r["step_s"] for r in recs if r.get("event") == "train"
              and isinstance(r.get("step_s"), (int, float))]
        # drop the compile-polluted first interval of each program
        return statistics.median(ss[2:]) if len(ss) > 4 \
            else statistics.median(ss)

    problems = []
    runs = {}
    for policy in ("static", "adaptive"):
        t = Trainer(cfg(policy))
        t.train(t.total_steps - t.step)
        rep = validate_file(os.path.join(t.run_dir, "metrics.jsonl"),
                            strict=True)
        if not rep.ok:
            problems.append(f"{policy}: event stream invalid: "
                            f"{rep.errors[:3]}")
        runs[policy] = {
            "median_step_s": median_step_s(t.run_dir),
            "events": rep.events,
            "recompiles": (t.engine.recompiles if t.engine else 0),
            "budget_left": (t.engine.budget_left if t.engine else None),
            "decision_log": (t.engine.decision_log if t.engine else []),
        }
    a, s = runs["adaptive"], runs["static"]
    if a["recompiles"] > 8:
        problems.append(f"adaptive recompiles {a['recompiles']} > budget")
    slowdown = a["median_step_s"] / s["median_step_s"]
    if slowdown > 1.0 / SMOKE_TOLERANCE:
        problems.append(
            f"adaptive lost to static beyond tolerance: "
            f"median step_s {a['median_step_s']:.4f} vs "
            f"{s['median_step_s']:.4f} ({slowdown:.2f}x, "
            f"tolerance {1 / SMOKE_TOLERANCE:.2f}x)")
    return {
        "mode": "smoke", "runs": runs,
        "adaptive_over_static_step_s": round(slowdown, 4),
        "tolerance": round(1.0 / SMOKE_TOLERANCE, 4),
        "problems": problems, "ok": not problems,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--matrix", default=DEFAULT_MATRIX,
                    help="bench matrix artifact with per-arm cells")
    ap.add_argument("--horizon", type=int, default=120,
                    help="simulated log intervals per config")
    ap.add_argument("--measure", action="store_true",
                    help="price the per-arm matrix live with benchlib")
    ap.add_argument("--smoke", action="store_true",
                    help="CI arm: live mnistnet static-vs-adaptive run")
    ap.add_argument("--tag", default=None,
                    help="artifact suffix (default: matrix basename tag)")
    ap.add_argument("--out-dir", default=ARTIFACTS)
    args = ap.parse_args(argv)

    if args.smoke:
        from gaussiank_sgd_tpu import compile_cache, virtual_cpu
        virtual_cpu.provision(8)
        compile_cache.enable_compile_cache()
        import tempfile
        with tempfile.TemporaryDirectory() as td:
            result = run_smoke(td)
        tag = "smoke"
    elif args.measure:
        import jax
        configs = measure_matrix()
        tmp = os.path.join(args.out_dir, "policy_ab_measured_matrix.json")
        with open(tmp, "w") as f:
            json.dump([{"config": c["key"], "model": c["model"],
                        "platform": c["platform"],
                        "cells": [{"density": 0.001, "compressor": a,
                                   "dense_ms": c["dense_ms"],
                                   "sparse_ms": t}
                                  for a, t in c["arms"].items()]}
                       for c in configs], f, indent=1)
        result = run_replay(tmp, args.horizon)
        result["acceptance"] = evaluate(result)
        tag = f"measured_{jax.devices()[0].platform}"
    else:
        result = run_replay(args.matrix, args.horizon)
        result["acceptance"] = evaluate(result)
        tag = (args.tag or
               os.path.basename(args.matrix).replace("bench_matrix_", "")
               .replace(".json", ""))

    os.makedirs(args.out_dir, exist_ok=True)
    out = os.path.join(args.out_dir, f"policy_ab_{args.tag or tag}.json")
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    ok = result.get("ok", result.get("acceptance", {}).get("ok", False))
    summary = {
        "artifact": os.path.relpath(out, REPO), "ok": ok,
        **({"acceptance": {k: v for k, v in result["acceptance"].items()
                           if k != "strict_wins_sample"}}
           if "acceptance" in result else
           {"adaptive_over_static_step_s":
            result.get("adaptive_over_static_step_s"),
            "problems": result.get("problems")}),
    }
    print(json.dumps(summary, indent=1))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
