#!/usr/bin/env python3
"""Read what the limits of `correct` are set from, in one process.

    python3 benchmarks/calibrate.py --workload <cell> --seeds 1,2,3 [--control-seeds 1,2] [--fault-seeds 3]

For every seed: build the cell's trainers (the arms of its mix) as a run does, take them
through their first steps and the warm-up, free them, follow the same steps
with the plain reference, and print every number the comparison reads (the
sound runs' readings), with what the seed cost: the seconds of the
program's side and of the reference's, each by part, in the lines a run
prints (`seed <n> the program by part: ...`). For every control seed also
put the reference in the program's place in the nearest precision below the
configuration's (`float8` operands under bfloat16) and print the same
numbers for it (the control's readings). A limit goes above the sound runs' largest and below
the control's smallest (PERF.md section 2). For every fault seed the timed
path is broken underneath (`worker_rows_left_out`): the trainers are fed
batches in which one worker's rows repeat another's, and the reference sees
the rows as drawn. Each set of readings is then held to the configuration's
limits by `check.judge`, as a run holds its own, and a `VERDICT` line says
what came out: a sound seed has to read `correct true`, a control and a
fault seed `correct false`. The benchmark's own runs never run this; it
needs the chips the cell needs and refuses without them.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


@contextlib.contextmanager
def worker_rows_left_out(nworkers: int):
    """A part of the batch left out where the trainers take it: the last
    worker's rows (with one worker: the second half) repeat the first's.
    `TimedFeed` keeps the rows as drawn, which is what the reference sees."""
    import numpy as np
    from benchmarks import harness
    sound_next = harness.TimedFeed.__next__

    def next_with_rows_left_out(self):
        batch = sound_next(self)
        part = len(batch[0]) // max(2, nworkers)
        return tuple(np.concatenate([a[:-part], a[:part]]) for a in batch)

    harness.TimedFeed.__next__ = next_with_rows_left_out
    try:
        yield
    finally:
        harness.TimedFeed.__next__ = sound_next


def verdict(numbers: dict, limits: dict, judged=None) -> dict:
    """`check.judge` over `numbers` (over the limits named in `judged`, where
    the readings are the reference's own and have no state to count)."""
    from benchmarks import check
    if judged is not None:
        limits = {k: v for k, v in limits.items() if k in judged}
    ok, lines = check.judge(numbers, limits)
    return {"correct": bool(ok),
            "failed": [l.split()[1].rstrip(":") for l in lines
                       if l.endswith("FAILED") or "MISSING" in l]}


def readings(cell, seed, control: bool, expected_states=None,
             fault: bool = False, compile_log=None) -> dict:
    """One seed's readings, and what the seed cost: the seconds of the
    program's side, of the reference's and of the control's, each by part
    (`harness.Parts`, the lines a run prints), under `seconds`."""
    from benchmarks import check, harness
    out_dir = harness.make_out_dir()
    program, followed, lowered = (harness.Parts(compile_log)
                                  for _ in range(3))
    try:
        t0 = time.perf_counter()
        broken = (worker_rows_left_out(int(cell["mix"]["nworkers"]))
                  if fault else contextlib.nullcontext())
        with broken:
            arms, weights = harness.build_arms(cell, seed, out_dir, False,
                                               parts=program)
            for name, arm in arms.items():
                with program(f"{name} first steps"):
                    harness.first_steps(arm, cell["config_data"])
            with program("sparse warm-up"):
                harness.warm_up(arms["sparse"], cell["mix"])
        firsts = {n: types.SimpleNamespace(name=n, first=a.first)
                  for n, a in arms.items()}
        with program("trainers closed"):
            harness.close_arms(arms)
        t1 = time.perf_counter()
        config, mix = cell["config_data"], cell["mix"]
        head = config.get("head_leaf")
        sound_by_arm, control_by_arm = [], []
        out = {"seed": seed, "leaves": {"sound": {}, "control": {}},
               "losses": {}, "control_losses": {}}
        reference_s = control_s = 0.0
        # one arm after the other, as `check.run_check` goes, so that the
        # host holds one arm's vectors at a time
        for name, arm in firsts.items():
            t2 = time.perf_counter()
            with followed("the program's readings"):
                mine, batches, masks = check.take_readings(
                    arm, weights, config, expected_states)
            ref = check.reference_readings(config, mix, seed, batches, masks,
                                           weights, parts=followed)
            with followed("compare"):
                sound_by_arm.append(check.compare(
                    mine, ref, head, table=out["leaves"]["sound"]))
            out["losses"][name] = [mine[name]["losses"], ref[name]["losses"]]
            if name == "sparse":
                with followed("lost_entries"):
                    exact = dict(mine[name]["exact"])
                    exact["lost"] = check.lost_entries(
                        mine[name], ref[name], arm.first["k"])
            del mine
            reference_s += time.perf_counter() - t2
            if control:
                t3 = time.perf_counter()
                low = check.reference_readings(config, mix, seed, batches,
                                               masks, weights,
                                               precision="float8",
                                               parts=lowered)
                with lowered("compare"):
                    control_by_arm.append(check.compare(
                        low, ref, head, table=out["leaves"]["control"]))
                out["control_losses"][name] = low[name]["losses"]
                control_s += time.perf_counter() - t3
                del low
            del ref, batches, masks
        out["sound"] = check.worst(sound_by_arm)
        out["sound"].update(exact)
        out["fault"] = bool(fault)
        # no window here: its two counts stand at 0, as in a sound run
        out["verdict"] = verdict(
            dict(out["sound"], compiles_in_window=0, failed_steps=0),
            config["limits"])
        out.update(program_s=t1 - t0, reference_s=reference_s)
        out["seconds"] = {"program": program.record(t1 - t0),
                          "reference": followed.record(reference_s)}
        harness.say(program.line(f"seed {seed} the program by part:",
                                 t1 - t0))
        harness.say(followed.line(f"seed {seed} the reference by part:",
                                  reference_s))
        if control:
            out["seconds"]["control"] = lowered.record(control_s)
            harness.say(lowered.line(f"seed {seed} the control by part:",
                                     control_s))
            out["control"] = check.worst(control_by_arm)
            out["control_verdict"] = verdict(out["control"], config["limits"],
                                             judged=out["control"])
            out["control_s"] = control_s
        else:
            del out["control_losses"], out["leaves"]["control"]
        return out
    finally:
        harness.remove_out_dir(out_dir)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmarks/calibrate.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
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
    compile_log = harness.CompileLog()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    faults = {int(s) for s in args.fault_seeds.split(",") if s}
    rows = []
    for seed in seeds:
        row = readings(cell, seed, seed in controls, fault=seed in faults,
                       compile_log=compile_log)
        rows.append(row)
        for kind, key in (("fault" if row["fault"] else "sound", "verdict"),
                          ("control", "control_verdict")):
            if key in row:
                print(f"VERDICT seed {seed} {kind}: correct "
                      f"{str(row[key]['correct']).lower()}; failed "
                      f"{row[key]['failed']}", flush=True)
        print("READINGS " + json.dumps({k: v for k, v in row.items()
                                        if k != "leaves"}), flush=True)
        if args.out:
            os.makedirs(os.path.dirname(args.out), exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    keys = [k for k, v in rows[0]["sound"].items()
            if isinstance(v, (int, float))]
    for k in keys:
        sound = [r["sound"][k] for r in rows if not r["fault"]]
        line = f"SUMMARY {k}:"
        if sound:
            line += f" sound min {min(sound):.6g} max {max(sound):.6g}"
        broke = [r["sound"][k] for r in rows if r["fault"]]
        if broke:
            line += f"; fault min {min(broke):.6g} max {max(broke):.6g}"
        ctl = [r["control"][k] for r in rows
               if "control" in r and k in r["control"]]
        if ctl:
            line += f"; control min {min(ctl):.6g} max {max(ctl):.6g}"
        print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
