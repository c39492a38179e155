#!/usr/bin/env python3
"""Read what the limits of `correct` are set from, in one process.

    python3 benchmarks/calibrate.py --workload <cell> --seeds 1,2,3 [--control-seeds 1,2,3]

For every seed: build the cell's two trainers as a run does, take them
through their first steps and the warm-up, free them, follow the same steps
with the plain reference, and print every number the comparison reads (the
sound runs' readings). For every control seed also put the reference in the
program's place in the nearest precision below the configuration's
(`float8` operands under bfloat16) and print the same numbers for it (the
control's readings). A limit goes above the sound runs' largest and below
the control's smallest (PERF.md section 2). The benchmark's own runs never
run this; it needs the chips the cell needs and refuses without them.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def readings(cell, seed, control: bool, expected_states=None) -> dict:
    from benchmarks import check, harness
    out_dir = harness.make_out_dir()
    try:
        t0 = time.perf_counter()
        arms, weights = harness.build_arms(cell, seed, out_dir, False)
        for name in ("dense", "sparse"):
            harness.first_steps(arms[name], cell["config_data"])
        harness.warm_up(arms["sparse"], cell["mix"])
        firsts = {n: types.SimpleNamespace(name=n, first=a.first)
                  for n, a in arms.items()}
        harness.close_arms(arms)
        t1 = time.perf_counter()
        config, mix = cell["config_data"], cell["mix"]
        mine = {n: check.program_readings(a, weights, config, expected_states)
                for n, a in firsts.items()}
        batches = {n: a.first["batches"] for n, a in firsts.items()}
        masks = firsts["sparse"].first["masks"]
        ref = check.reference_readings(config, mix, seed, batches, masks,
                                       weights)
        sound = check.compare(mine, ref, config.get("head_leaf"))
        leaves = {"sound": check.leaf_table(mine, ref)}
        sound.update(mine["sparse"]["exact"])
        sound["lost"] = check.lost_entries(mine["sparse"], ref["sparse"],
                                           firsts["sparse"].first["k"])
        out = {"seed": seed, "sound": sound, "leaves": leaves,
               "losses": {a: [mine[a]["losses"], ref[a]["losses"]]
                          for a in ref},
               "program_s": t1 - t0, "reference_s": time.perf_counter() - t1}
        if control:
            t2 = time.perf_counter()
            low = check.reference_readings(config, mix, seed, batches, masks,
                                           weights, precision="float8")
            out["control"] = check.compare(low, ref, config.get("head_leaf"))
            leaves["control"] = check.leaf_table(low, ref)
            out["control_losses"] = {a: low[a]["losses"] for a in low}
            out["control_s"] = time.perf_counter() - t2
        return out
    finally:
        harness.remove_out_dir(out_dir)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmarks/calibrate.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from benchmarks import harness
    cell = harness.load_cell(args.workload)
    import jax
    if jax.default_backend() != "tpu" or jax.device_count() < cell["chips"]:
        print(f"benchmarks/calibrate.py: not run: needs {cell['chips']} TPU "
              f"chip(s), JAX has {jax.device_count()} "
              f"{jax.default_backend()} device(s)", file=sys.stderr)
        return 2
    from gaussiank_sgd_tpu.compile_cache import enable_compile_cache
    enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    rows = []
    for seed in seeds:
        row = readings(cell, seed, seed in controls)
        rows.append(row)
        print("READINGS " + json.dumps({k: v for k, v in row.items()
                                        if k != "leaves"}), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out), exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    keys = [k for k, v in rows[0]["sound"].items()
            if isinstance(v, (int, float))]
    for k in keys:
        sound = [r["sound"][k] for r in rows]
        line = f"SUMMARY {k}: sound min {min(sound):.6g} max {max(sound):.6g}"
        ctl = [r["control"][k] for r in rows
               if "control" in r and k in r["control"]]
        if ctl:
            line += f"; control min {min(ctl):.6g} max {max(ctl):.6g}"
        print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
