"""Where does the 57M-param sparse-step overhead go? (config 5 deep-dive)

The transformer config's sparse:dense ratio is window-dependent (0.86-1.10)
because the selection overhead is ~constant absolute ms while dense
fwd+bwd drifts with the shared chip. Before optimizing further (Pallas
fusion, EF-state restructure), this script decomposes the overhead by
running ABLATED compressors that each do a prefix of the full pipeline,
all interleaved in ONE bench_model run so the differences are drift-free:

  ef_only       EF accumulate + exchange of a FIXED k-slice (no selection,
                no residual scatter) — the floor every sparse step pays
  sel_nores     + abs + bf16 cast + approx_max_k + gather (residual = acc
                untouched: EF-INCORRECT, measurement only)
  approxtopk16  + the residual scatter-copy (the real selector)
  gaussian_warm the threshold-mask path (mask + key-trick pack + scatter)

Differences: (sel_nores - ef_only) = selection cost; (approxtopk16 -
sel_nores) = residual-write cost; (gaussian_warm - ef_only) = mask+pack
cost. Writes analysis/artifacts/sparse_ablation.json.

Run on the TPU box:  python analysis/sparse_ablation.py
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

ARTIFACTS = os.path.join(REPO, "analysis", "artifacts")


def main(argv=None):
    # the ef_only/sel_nores prefix probes live in benchlib.ablation_specs
    # (shared with analysis/bench_matrix.py's per-cell phase columns);
    # bench_model resolves their names directly
    from gaussiank_sgd_tpu.benchlib import bench_model
    from gaussiank_sgd_tpu.compile_cache import enable_compile_cache

    # persistent compile cache (works for the TPU backend too): a re-run
    # in a better drift window must not pay the ~20-min 57M-param compile
    # bill again
    enable_compile_cache()

    from gaussiank_sgd_tpu.benchlib import paired_delta_ms

    names = ("ef_only", "sel_nores", "approxtopk16", "gaussian_warm",
             "gaussian_fused")
    times = bench_model("transformer", "wmt", 64, 0.001, names,
                        n_steps=10, rounds=6)

    dense = times["dense"]
    ms = {k: round(1e3 * v, 3) for k, v in times.items()
          if isinstance(v, float) and not k.startswith("_")}

    # PAIRED per-round deltas — the shared drift-robust estimator
    # (benchlib.paired_delta_ms; see its docstring for why min-of-rounds
    # deltas are invalid here)
    rnds = times["_rounds"]

    def delta_ms(a, b):
        return paired_delta_ms(rnds, a, b)

    out = {
        "model": "transformer 57M, b=64, density 0.001",
        "ms": ms,
        "decomposition_ms": {
            "dense_fwd_bwd_update": ms["dense"],
            "ef_exchange_floor": delta_ms("ef_only", "dense"),
            "abs_cast_select_gather": delta_ms("sel_nores", "ef_only"),
            "residual_scatter_copy": delta_ms("approxtopk16", "sel_nores"),
            "warm_mask_pack_total": delta_ms("gaussian_warm", "ef_only"),
            # the r4 north-star kernel (ops/pallas_pack.py): fused
            # select+pack overhead over the same EF+exchange floor
            "fused_kernel_pack_total": delta_ms("gaussian_fused", "ef_only"),
            "fused_total_overhead_vs_dense": delta_ms("gaussian_fused",
                                                      "dense"),
            "warm_total_overhead_vs_dense": delta_ms("gaussian_warm",
                                                     "dense"),
        },
        "methodology": "median over rounds of per-round paired deltas; "
                       "every variant timed inside every rotated round",
        "ratios": {k: round(dense / times[k], 4) for k in names},
    }
    os.makedirs(ARTIFACTS, exist_ok=True)
    with open(os.path.join(ARTIFACTS, "sparse_ablation.json"), "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out, indent=2))
    return out


if __name__ == "__main__":
    main()
