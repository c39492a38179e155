"""The BASELINE config matrix, measured (SURVEY.md §6, VERDICT r1 item 4).

For each BASELINE config's model shape, measures on the available chip:
dense step time + sparse step time across a density sweep
{0.1, 0.01, 0.001} for the two headline selector families (hardware
approx-top-k and GaussianK threshold estimation), reporting
examples/sec/chip and the sparse:dense ratio for every cell.

Single-chip scope: this machine exposes ONE TPU chip (SURVEY.md §0), so
these are per-chip compute+compression numbers — the collective cost at
8/32/64-way rides ICI and is validated functionally on the virtual mesh
(tests/) while its byte volume is characterized analytically in the
metrics (bytes_sent) and in analysis/convergence_parity.py.

Writes analysis/artifacts/bench_matrix.json and a markdown table to
analysis/artifacts/bench_matrix.md (pasted into BASELINE.md).

Run on the TPU box: python analysis/bench_matrix.py [--quick]
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

# (config name, model, dataset, per-chip batch, model_kwargs, n_steps,
#  bucket policy, bucket size). All configs use the whole-model bucket:
# analysis/lm_fastpath.py measured it BEATING the uniform 4M-chunk vmapped
# plan in-run on both LM configs (uniform pays its own per-chunk pack
# overhead without reducing the dominant full-buffer EF/mask passes), and
# with it configs 4/5 clear the >=0.90 target at density 0.001
# (approxtopk 0.99/0.94, approxtopk16 1.20/1.10, gaussian_warm 0.94/0.95).
CONFIGS = [
    ("config1_resnet20", "resnet20", "cifar10", 1024, {}, 40, "greedy", None),
    ("config2_vgg16", "vgg16", "cifar10", 256, {}, 20, "greedy", None),
    ("config3_resnet50", "resnet50", "imagenet", 64, {}, 10, "greedy", None),
    ("config4_lstm_ptb", "lstm", "ptb", 160, {}, 10, "greedy", None),
    # b32 = the exp_configs/config5*.json per-chip batch (VERDICT r3 item 8)
    ("config5_transformer", "transformer", "wmt", 32, {}, 10, "greedy", None),
]
DENSITIES = (0.1, 0.01, 0.001)
COMPRESSORS = ("approxtopk", "gaussian", "gaussian_warm", "approxtopk16",
               "gaussian_fused")
# prefix probe for the per-cell phase decomposition (benchlib.ablation_specs)
PROBE = "ef_only"


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--quick", action="store_true",
                   help="one density, fewer rounds (smoke)")
    p.add_argument("--configs", default=None,
                   help="comma-separated substring filter on config names")
    p.add_argument("--tag", default="",
                   help="suffix for the artifact filenames (e.g. 'paired' "
                        "-> bench_matrix_paired.{json,md}) so a re-run "
                        "never clobbers a window it should be compared "
                        "against")
    p.add_argument("--densities", default=None,
                   help="comma list overriding the density sweep "
                        "(e.g. '0.1,0.01')")
    args = p.parse_args(argv)

    import jax

    from gaussiank_sgd_tpu.benchlib import (bench_model, mfu,
                                            noise_floored_delta_ms)
    from gaussiank_sgd_tpu.compile_cache import enable_compile_cache

    # persistent compile cache across matrix runs/windows (TPU backend too)
    enable_compile_cache()

    if args.densities:
        densities = tuple(float(d) for d in args.densities.split(","))
    else:
        densities = (0.001,) if args.quick else DENSITIES
    rounds = 3 if args.quick else 6
    suffix = f"_{args.tag}" if args.tag else ""
    os.makedirs(ARTIFACTS, exist_ok=True)

    results = []
    for name, model, dataset, batch, mkw, n_steps, policy, bsize in CONFIGS:
        if args.configs and not any(s in name for s in
                                    args.configs.split(",")):
            continue
        row = {"config": name, "model": model, "batch_per_chip": batch,
               "bucket_policy": policy, "bucket_size": bsize,
               "platform": jax.devices()[0].platform, "cells": []}
        for d in densities:
            print(f"=== {name} density={d} ===", flush=True)
            from gaussiank_sgd_tpu.ops.pallas_pack import supports_density
            comps = tuple(c for c in COMPRESSORS
                          if c != "gaussian_fused" or supports_density(d))
            times = bench_model(model, dataset, batch, d,
                                comps + (PROBE,),
                                n_steps=n_steps, rounds=rounds,
                                model_kwargs=mkw, bucket_policy=policy,
                                bucket_size=bsize)
            dense = times["dense"]
            flops = times.get("_dense_step_flops")
            peak = times.get("_peak_flops")
            rnds = times.get("_rounds", {})
            for c in comps:
                md, ms = mfu(flops, dense, peak), mfu(flops, times[c], peak)
                # round-paired ratios (dense and sparse timed within the
                # SAME rotated round) — robust to cross-window drift, the
                # failure mode VERDICT r2 weak #6 documents
                paired = [dn / sp for dn, sp in
                          zip(rnds.get("dense", []), rnds.get(c, []))]
                row["cells"].append({
                    "density": d, "compressor": c,
                    "dense_ms": round(1e3 * dense, 3),
                    "sparse_ms": round(1e3 * times[c], 3),
                    "ratio": round(dense / times[c], 4),
                    "ratio_median_paired": (round(
                        statistics.median(paired), 4) if paired else None),
                    "ratio_spread_paired": (
                        [round(min(paired), 4), round(max(paired), 4)]
                        if paired else None),
                    "ex_per_s_chip": round(batch / times[c], 1),
                    "flops_per_step": flops,
                    "mfu_dense": round(md, 4) if md else None,
                    "mfu_sparse": round(ms, 4) if ms else None,
                    # per-phase breakdown (VERDICT r3 item 6), from the
                    # ef_only prefix probe timed in the same rotated
                    # rounds: fwd+bwd+update = the dense program;
                    # exchange = the fixed-k EF floor's delta over it;
                    # select+pack = this selector's delta over the floor.
                    # All three phase figures come from the SAME estimator
                    # (per-round medians / paired-median deltas) so the
                    # column reconciles with itself. Deltas below the
                    # cell's own round-to-round noise floor report None
                    # ("< noise" in the table) instead of a physically
                    # impossible negative duration (VERDICT r5 weak #5;
                    # benchlib.noise_floored_delta_ms)
                    "fwd_bwd_ms": (round(1e3 * statistics.median(
                        rnds["dense"]), 3) if rnds.get("dense") else None),
                    "exchange_ms": noise_floored_delta_ms(
                        rnds, PROBE, "dense"),
                    "select_pack_ms": noise_floored_delta_ms(
                        rnds, c, PROBE),
                })
            print(json.dumps(row["cells"][-len(comps):]), flush=True)
        results.append(row)
        # write incrementally: an hour of chip measurements must survive a
        # crash in a later config
        with open(os.path.join(ARTIFACTS,
                               f"bench_matrix{suffix}.json"), "w") as f:
            json.dump(results, f, indent=2)

    table = render_md(results)
    with open(os.path.join(ARTIFACTS, f"bench_matrix{suffix}.md"), "w") as f:
        f.write(table + "\n")
    print(table)
    return results


def render_md(results) -> str:
    lines = ["| Config | density | compressor | dense ms | sparse ms | "
             "sparse:dense | paired median | paired spread | ex/s/chip | "
             "MFU dense | MFU sparse | phases fb/ex/sel ms |",
             "|---|---|---|---|---|---|---|---|---|---|---|---|"]
    for row in results:
        for c in row["cells"]:
            fmt = lambda v: f"{100 * v:.1f}%" if v else "—"
            spread = c.get("ratio_spread_paired")
            lines.append(
                f"| {row['config']} (b={row['batch_per_chip']}) "
                f"| {c['density']} | {c['compressor']} | {c['dense_ms']} "
                f"| {c['sparse_ms']} | {c['ratio']} "
                f"| {c.get('ratio_median_paired') or '—'} "
                f"| {f'{spread[0]}–{spread[1]}' if spread else '—'} "
                f"| {c['ex_per_s_chip']} | {fmt(c['mfu_dense'])} "
                f"| {fmt(c['mfu_sparse'])} "
                f"| {c.get('fwd_bwd_ms') or '—'}"
                f"/{c.get('exchange_ms') if c.get('exchange_ms') is not None else '< noise'}"
                f"/{c.get('select_pack_ms') if c.get('select_pack_ms') is not None else '< noise'} |")
    return "\n".join(lines)


if __name__ == "__main__":
    main()
