"""Benchmark harness — prints ONE JSON line for the driver.

Headline metric (BASELINE north-star, SURVEY.md §6): sparse-step throughput
as a fraction of dense-step throughput on the same model/batch, target
>= 0.90 ("sparse must not lose to dense").

De-cherry-picked per VERDICT r2 item 6 and r3 item 2: the headline is the
MEDIAN-of-rounds ratio for THE framework's ex-ante default selector —
``compressors.registry.DEFAULT_SELECTOR`` (gaussian_fused: warm-started
GaussianK threshold + the Pallas fused select+pack kernel,
ops/pallas_pack.py) — the policy a user inherits without measuring, not a
per-window winner. Min-of-rounds and the best-of-3-selectors winner are
reported as SECONDARY fields. detail.configs carries the same
fixed-selector median/min ratio plus MFU for ALL FIVE BASELINE configs with
per-round dispersion, so no favorable cell can carry the number.

Methodology (gaussiank_sgd_tpu/benchlib.py): N steps per dispatch via a
jitted fori_loop, scalar fence, interleaved rotated rounds. MFU = dense-step
HLO FLOPs / (step time x chip bf16 peak) — the absolute-performance leg
(VERDICT r2 item 2).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from typing import List, Optional

import jax

from gaussiank_sgd_tpu.compressors import DEFAULT_SELECTOR
from gaussiank_sgd_tpu.telemetry import EventBus, JSONLExporter
from gaussiank_sgd_tpu.telemetry.history import (append_history,
                                                 build_history_record,
                                                 git_revision)

FIXED = DEFAULT_SELECTOR        # the codified ex-ante policy (registry.py)
SWEEP = (FIXED, "gaussian_warm", "approxtopk16")

# (key, model, dataset, per-chip batch, n_steps, rounds PER WINDOW)
# Rounds per cell sized to the cell's observed paired-ratio dispersion
# (bench_matrix_r5: vgg/lstm spreads 0.69-1.17 at 5 rounds) — the r5
# dense-step optimizations shrank several denominators to <15 ms, where
# per-round chip drift is proportionally larger, so the noisier cells get
# more rounds to keep the MEDIAN stable.
CONFIGS = (
    ("resnet20", "resnet20", "cifar10", 1024, 40, 3),
    ("vgg16", "vgg16", "cifar10", 256, 20, 4),
    ("resnet50", "resnet50", "imagenet", 64, 10, 3),
    ("lstm_ptb", "lstm", "ptb", 160, 10, 4),
    # b32 = the exp_configs/config5*.json per-chip batch (VERDICT r3 item 8:
    # bench and training config must share one operating point)
    ("transformer_wmt", "transformer", "wmt", 32, 10, 4),
)
# Measurement power (ISSUE 6 satellite): every config's round block runs
# WINDOWS independent times; the binding per-config ratio is the MIN over
# the windows' paired medians, so slow drift between windows cannot carry
# a >= 0.90 claim that a re-measurement would retract.
WINDOWS = 2

# --smoke: one tiny config, CI-sized (seconds, not minutes, on CPU) — the
# point is exercising the full harness + telemetry emission path, not a
# meaningful throughput number. Smoke runs on a uniform 8192-element bucket
# plan: small enough to pass the wire gate (chunk <= 65536, parallel/
# wire.py) AND block-aligned for the fused EF+select kernel, so CI
# exercises — and asserts on — the packed u16+bf16 exchange end to end.
SMOKE_CONFIGS = (
    ("mnistnet", "mnistnet", "mnist", 8, 2, 2),
)
SMOKE_BUCKETS = {"bucket_policy": "uniform", "bucket_size": 8192}


def _ratios(times, name):
    """median/min sparse:dense ratios from per-round samples, paired by
    round index (both programs ran inside every round), plus the
    per-window paired medians and their min — the binding per-config
    number (ISSUE 6 measurement-power satellite)."""
    dr = times["_rounds"]["dense"]
    sr = times["_rounds"][name]
    per_round = [d / s for d, s in zip(dr, sr)]
    dw = times.get("_windows", {}).get("dense") or [dr]
    sw = times.get("_windows", {}).get(name) or [sr]
    window_medians = [
        round(statistics.median([d / s for d, s in zip(dwin, swin)]), 4)
        for dwin, swin in zip(dw, sw)]
    return {
        "ratio_median": round(statistics.median(per_round), 4),
        "ratio_min": round(min(per_round), 4),
        "ratio_max": round(max(per_round), 4),
        # the measurement-protocol record (VERDICT r5 weak #7): every
        # reported median carries its round count and spread, so a
        # BENCH artifact can never present a 1-round point as a median
        "rounds": len(per_round),
        "round_ratios": [round(r, 4) for r in per_round],
        # per-window paired medians; the config's binding ratio is their
        # MIN, so a >= 0.90 claim survives re-measurement
        "windows": len(window_medians),
        "window_medians": window_medians,
        "ratio_window_min": min(window_medians),
    }


def _load_roofline(artifacts: str):
    """Per-config floor_ms from analysis/roofline.py's artifact, iff it
    was priced on THIS platform (a CPU-bandwidth floor says nothing
    about a TPU overhead, and vice versa); {} when absent/foreign."""
    path = os.path.join(artifacts, "roofline.json")
    if not os.path.exists(path):
        return {}
    try:
        with open(path) as f:
            roof = json.load(f)
        if roof.get("platform") != jax.devices()[0].platform:
            return {}
        return {k: c["floor_ms"] for k, c in roof["configs"].items()}
    except (ValueError, KeyError, OSError):
        return {}


def main(argv: Optional[List[str]] = None):
    from gaussiank_sgd_tpu.benchlib import bench_model, bench_overlap, mfu
    from gaussiank_sgd_tpu.compile_cache import enable_compile_cache

    # default [] (not sys.argv): the test harness calls main() inside a
    # pytest process whose argv is pytest's, not ours
    ap = argparse.ArgumentParser(prog="bench.py")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny single-config run for CI: exercises the "
                         "harness + telemetry emission, not a real number")
    ap.add_argument("--configs", nargs="*", default=None,
                    help="subset of config keys to run (default: all; "
                         "feasibility valve for small hosts — the "
                         "artifact records which configs ran)")
    ap.add_argument("--overlap-arm", action="store_true",
                    help="also time each config's off-vs-auto schedule "
                         "pair on a pipeline-eligible uniform plan "
                         "(ISSUE 7; always on under --smoke)")
    ap.add_argument("--history", default=None, metavar="PATH",
                    help="bench-history JSONL to append this run's record "
                         "to (default: analysis/artifacts/"
                         "bench_history.jsonl; the regression sentinel's "
                         "input — analysis/regression_sentinel.py)")
    ap.add_argument("--no-history", action="store_true",
                    help="skip the history append (throwaway runs)")
    args = ap.parse_args([] if argv is None else argv)

    # persistent compile cache: repeated driver runs skip the multi-minute
    # 20-60M-param compiles (drift windows change, programs don't)
    enable_compile_cache()

    artifacts = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "analysis", "artifacts")
    os.makedirs(artifacts, exist_ok=True)
    # machine-readable record stream (docs/OBSERVABILITY.md): one
    # schema-validated bench_model event per config + a bench_summary,
    # through the same exporter interface the trainer uses. mode='w': each
    # run is a fresh single-run stream; validate=True: a schema drift
    # fails HERE (and in the CI smoke), not in a downstream parser.
    bus = EventBus([JSONLExporter(
        os.path.join(artifacts, "bench_events.jsonl"), mode="w")],
        validate=True)

    density = 0.001
    detail_configs = {}
    headline = None
    floors = _load_roofline(artifacts)
    configs = SMOKE_CONFIGS if args.smoke else CONFIGS
    for key, model, dataset, batch, n_steps, rounds in configs:
        if args.configs and key not in args.configs:
            continue
        # the flagship config also runs the 3-selector sweep (secondary
        # winner field); the other configs run the fixed selector only to
        # bound driver wall-clock
        comps = SWEEP if key == "resnet20" else (FIXED,)
        times = bench_model(model, dataset, batch, density, comps,
                            n_steps=n_steps, rounds=rounds, windows=WINDOWS,
                            **(SMOKE_BUCKETS if args.smoke else {}))
        flops = times.get("_dense_step_flops")
        peak = times.get("_peak_flops")
        md = mfu(flops, times["dense"], peak)
        ms = mfu(flops, times[FIXED], peak)
        cell = {
            "compressor": FIXED,
            "dense_step_ms": round(1e3 * times["dense"], 3),
            "sparse_step_ms": round(1e3 * times[FIXED], 3),
            "ex_per_s_chip": round(batch / times[FIXED], 1),
            "mfu_dense": round(md, 4) if md else None,
            "mfu_sparse": round(ms, 4) if ms else None,
            **_ratios(times, FIXED),
        }
        # achieved compression overhead vs the per-config HBM floor
        # (analysis/roofline.py; ISSUE 4 gate: <= 1.3x floor for any
        # config under 0.90)
        cell["overhead_ms"] = round(cell["sparse_step_ms"]
                                    - cell["dense_step_ms"], 3)
        # wire accounting rides next to every bytes claim (parallel/wire.py
        # protocol: a bytes number never travels without its format name)
        ex = times.get("_exchange", {}).get(FIXED, {})
        cell["wire_format"] = ex.get("wire_format")
        cell["bytes_sent"] = ex.get("bytes_sent")
        # which step schedule the main sparse arm compiled to (ISSUE 7:
        # the greedy contract plan is pipeline-ineligible, so this stays
        # "off" unless the plan is uniform multi-chunk)
        cell["overlap"] = ex.get("overlap")
        if key in floors:
            cell["roofline_floor_ms"] = floors[key]
            cell["overhead_vs_floor"] = (
                round(cell["overhead_ms"] / floors[key], 3)
                if floors[key] > 0 else None)
        if key == "resnet20":
            winner = min(SWEEP, key=lambda c: times[c])
            cell["winner_secondary"] = {
                "compressor": winner,
                **_ratios(times, winner),
                "all_sparse_ms": {c: round(1e3 * times[c], 3)
                                  for c in SWEEP},
            }
            headline = cell
        detail_configs[key] = cell
        bus.emit("bench_model", key=key, model=model, dataset=dataset,
                 batch=batch, compressor=FIXED,
                 dense_step_ms=cell["dense_step_ms"],
                 sparse_step_ms=cell["sparse_step_ms"],
                 ratio_median=cell["ratio_median"],
                 ratio_min=cell["ratio_min"],
                 ratio_max=cell["ratio_max"],
                 rounds=cell["rounds"],
                 windows=cell["windows"],
                 window_medians=cell["window_medians"],
                 ratio_window_min=cell["ratio_window_min"],
                 ex_per_s_chip=cell["ex_per_s_chip"],
                 mfu_dense=cell["mfu_dense"],
                 mfu_sparse=cell["mfu_sparse"],
                 overhead_ms=cell["overhead_ms"],
                 roofline_floor_ms=cell.get("roofline_floor_ms"),
                 overhead_vs_floor=cell.get("overhead_vs_floor"),
                 wire_format=cell["wire_format"],
                 bytes_sent=cell["bytes_sent"],
                 overlap=cell["overlap"])
        print(f"# {key}: window_min {cell['ratio_window_min']} "
              f"median {cell['ratio_median']} "
              f"min {cell['ratio_min']} mfu_dense {cell['mfu_dense']}",
              flush=True)
        if args.smoke:
            # CI acceptance (ISSUE 5): the smoke plan is wire-eligible by
            # construction, so the measured payload must be <= 0.55x the
            # fp32+i32 format at identical k (8 bytes/entry; the fixed
            # selector packs exactly total_k entries). ValueError, not
            # assert: the gate must fire under -O too (repo convention).
            fp32_bytes = ex["total_k"] * 8
            if (ex.get("wire_format") != "u16bf16"
                    or ex["bytes_sent"] > 0.55 * fp32_bytes):
                raise ValueError(
                    f"smoke wire gate failed: wire_format="
                    f"{ex.get('wire_format')!r}, bytes_sent="
                    f"{ex.get('bytes_sent')} vs fp32+i32 {fp32_bytes} "
                    f"(need u16bf16 and <= 0.55x)")

        if args.overlap_arm or args.smoke:
            # ISSUE-7 overlap arm: the same model/selector under both
            # step schedules on one pipeline-eligible uniform plan, each
            # with its exchange-ablated twin, all in the same rotated
            # rounds (benchlib.bench_overlap) — the per-config measured
            # answer to "how much exchange time does the pipeline hide"
            ob = bench_overlap(
                model, dataset, batch, density, FIXED,
                n_steps=n_steps, rounds=rounds, windows=WINDOWS,
                bucket_size=(SMOKE_BUCKETS["bucket_size"] if args.smoke
                             else 1 << 22))
            om, oe = ob["_meta"], ob["exposed_exchange_ms"]
            arm = {
                "seq_step_ms": round(1e3 * ob["seq"], 3),
                "pipe_step_ms": round(1e3 * ob["pipe"], 3),
                "pipe_vs_seq": round(ob["seq"] / ob["pipe"], 4),
                "exposed_seq_ms": oe["seq"],
                "exposed_pipe_ms": oe["pipe"],
                "seq_overlap": om["seq_overlap"],
                "pipe_overlap": om["pipe_overlap"],
                "bucket_size": om["bucket_size"],
                "n_buckets": om["n_buckets"],
                "wire_format": om.get("wire_format"),
                "bytes_sent": om.get("pipe_bytes_sent"),
                "overlapped_bytes_sent": om.get("overlapped_bytes_sent"),
            }
            cell["overlap_arm"] = arm
            bus.emit("bench_overlap", key=key, model=model,
                     compressor=FIXED, rounds=rounds, windows=WINDOWS,
                     **{k: v for k, v in arm.items() if v is not None})
            print(f"# {key} overlap arm: seq {arm['seq_step_ms']} ms "
                  f"(exposed {arm['exposed_seq_ms']}) vs pipe "
                  f"{arm['pipe_step_ms']} ms (exposed "
                  f"{arm['exposed_pipe_ms']}), x{arm['pipe_vs_seq']}",
                  flush=True)
            if args.smoke and (arm["pipe_overlap"] != "pipelined"
                               or arm["seq_overlap"] != "off"
                               or not arm["overlapped_bytes_sent"]):
                # CI acceptance (ISSUE 7): the smoke plan is pipeline-
                # eligible by construction, so the 'auto' build must have
                # compiled the pipelined schedule and launched payload
                # bytes from inside the scan body
                raise ValueError(
                    f"smoke overlap gate failed: seq_overlap="
                    f"{arm['seq_overlap']!r}, pipe_overlap="
                    f"{arm['pipe_overlap']!r}, overlapped_bytes_sent="
                    f"{arm['overlapped_bytes_sent']}")

    # The contract is "EVERY config >= 0.90" (BASELINE.json metric), so the
    # reportable scalar is the MIN over config binding ratios — and each
    # config's binding ratio is the MIN of its per-window paired medians
    # (VERDICT r4 item 2; ISSUE 6 measurement-power satellite). The
    # flagship resnet20 cell stays in detail.
    worst_key, worst = min(detail_configs.items(),
                           key=lambda kv: kv[1]["ratio_window_min"])
    value = worst["ratio_window_min"]
    bus.emit("bench_summary",
             metric="sparse_vs_dense_step_throughput_ratio", value=value,
             worst_config=worst_key, smoke=args.smoke,
             windows=WINDOWS,
             rounds=sum(c["rounds"] for c in detail_configs.values()))
    bus.close()
    result = {
        "metric": "sparse_vs_dense_step_throughput_ratio",
        "value": value,
        "unit": "ratio",
        "vs_baseline": round(value / 0.90, 4),
        "detail": {
            "headline": f"WORST-config min-over-{WINDOWS}-windows paired "
                        f"median ratio ({worst_key}) over all 5 BASELINE "
                        f"configs, ex-ante default selector {FIXED} "
                        f"(registry.DEFAULT_SELECTOR policy), "
                        f"density {density}",
            "worst_config": worst_key,
            "worst_config_ratio_window_min": worst["ratio_window_min"],
            "worst_config_ratio_median": worst["ratio_median"],
            "flagship_ratio_median": (headline["ratio_median"]
                                      if headline else None),
            "configs": detail_configs,
            "methodology": "N-step fori_loop per dispatch, scalar fence, "
                           "interleaved rotated rounds grouped into "
                           f"{WINDOWS} windows; ratios paired per round; "
                           "per-window medians, min-across-windows "
                           "headline, pooled median secondary",
            "platform": jax.devices()[0].platform,
            "n_devices": 1,
        },
    }
    # full per-round detail -> artifact (the driver's record keeps only a
    # tail of stdout, which truncated the r3 multi-KB line mid-JSON); the
    # FINAL stdout line stays compact enough to survive any tail window
    with open(os.path.join(artifacts, "bench_last.json"), "w") as f:
        json.dump(result, f, indent=2)
    # cross-run trajectory record (telemetry/history.py): the sentinel
    # compares this run against the committed history with the same
    # noise-floored machinery the bench's own deltas use
    if not args.no_history:
        hist_path = args.history or os.path.join(artifacts,
                                                 "bench_history.jsonl")
        append_history(hist_path, build_history_record(
            result, smoke=args.smoke, ts=time.time(),
            git_rev=git_revision(os.path.dirname(os.path.abspath(
                __file__)))))
    compact = {
        "metric": result["metric"], "value": value, "unit": "ratio",
        "vs_baseline": result["vs_baseline"],
        "detail": {
            "policy": f"fixed ex-ante default selector {FIXED}; value = "
                      f"worst-config min-over-window medians ({worst_key})",
            "worst_config": worst_key,
            "worst_config_ratio_window_min": worst["ratio_window_min"],
            "worst_config_ratio_median": worst["ratio_median"],
            "config_window_mins": {k: c["ratio_window_min"]
                                   for k, c in detail_configs.items()},
            "config_medians": {k: c["ratio_median"]
                               for k, c in detail_configs.items()},
            # spread + rounds per config (VERDICT r5 weak #7): the
            # median's dispersion travels with the claim
            "config_spreads": {k: [c["ratio_min"], c["ratio_max"]]
                               for k, c in detail_configs.items()},
            "rounds": {k: c["rounds"] for k, c in detail_configs.items()},
            "overhead_vs_floor": {k: c["overhead_vs_floor"]
                                  for k, c in detail_configs.items()
                                  if c.get("overhead_vs_floor")
                                  is not None} or None,
            # overlap arm (ISSUE 7), configs that ran it: measured
            # exposed exchange under each schedule (None = below noise)
            "overlap_arm": {k: {"exposed_seq_ms":
                                c["overlap_arm"]["exposed_seq_ms"],
                                "exposed_pipe_ms":
                                c["overlap_arm"]["exposed_pipe_ms"],
                                "pipe_vs_seq":
                                c["overlap_arm"]["pipe_vs_seq"]}
                            for k, c in detail_configs.items()
                            if "overlap_arm" in c} or None,
            "platform": jax.devices()[0].platform,
            "full_detail": "analysis/artifacts/bench_last.json",
        },
    }
    print(json.dumps(compact))
    return result


if __name__ == "__main__":
    main(sys.argv[1:])
