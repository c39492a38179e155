#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One new process on a machine that holds the cell's chips. It refuses to run
(exit code 2, no result line) unless JAX's default backend is a TPU with at
least the cell's chips and a `device_kind` that `peaks.json` knows. The last
line of its standard output is the result object of the benchmark's contract;
everything before it is for people. `harness.py` has the parts, `check.py`
the comparison that decides `correct`, `PERF.md` what the numbers mean.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up is counted from here

import argparse
import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


MARKS = []


def mark(label: str) -> None:
    """The stretch of start-up that has just ended, with the clock and the
    host's peak resident size: the first parts of the run's `set-up by
    part` line."""
    from benchmarks import harness
    MARKS.append((label, time.perf_counter(), harness.host_peak_gib()))


def refuse(why: str) -> None:
    print(f"benchmarks/run.py: not run: {why}", file=sys.stderr, flush=True)
    raise SystemExit(2)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmarks/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    for need in ("gaussiank_sgd_tpu", "BENCHMARK.json"):
        if not os.path.exists(os.path.join(ROOT, need)):
            refuse(f"{need} is not beside benchmarks/: the benchmark measures "
                   f"the program of its checkout and nothing else")

    from benchmarks import harness
    cell = harness.load_cell(args.workload)

    import jax
    mark("python and jax import")
    if jax.default_backend() != "tpu":
        refuse(f"JAX's default backend is {jax.default_backend()!r}, not a "
               f"TPU; a CPU's number is never written under a device "
               f"metric's name")
    if jax.device_count() < cell["chips"]:
        refuse(f"{cell['name']} needs {cell['chips']} chip(s), JAX sees "
               f"{jax.device_count()}")
    kind = jax.devices()[0].device_kind
    try:
        peaks = harness.load_peaks(kind)
    except KeyError as e:
        refuse(str(e))

    mark("backend start")
    from gaussiank_sgd_tpu.compile_cache import enable_compile_cache
    from gaussiank_sgd_tpu import train as _program  # noqa: F401
    mark("program import")
    cache_dir = enable_compile_cache()
    compile_log = harness.CompileLog()
    say = harness.say
    say(f"cell {cell['name']}: config {cell['config']}, mix "
        f"{cell['traffic']}, {cell['chips']} chip(s) of {kind!r}; seed "
        f"{args.seed}, {args.seconds:g}s, trace {args.trace}; compile cache "
        f"{cache_dir}")

    out_dir = harness.make_out_dir()
    try:
        return _run(args, cell, peaks, compile_log, out_dir)
    finally:
        harness.remove_out_dir(out_dir)


def _run(args, cell, peaks, compile_log, out_dir) -> int:
    """Everything after the look for a chip (the tests start here). It
    builds, drives, totals and checks the arms that the mix's round names,
    and no other."""
    from benchmarks import check, harness, trace_reduce
    say = harness.say
    config, mix = cell["config_data"], cell["mix"]
    setup = harness.Parts(compile_log)
    t_before, peak_before = T_START, 0.0
    for label, t, peak in MARKS:
        setup.add(label, t - t_before, host_peak=(peak_before, peak))
        t_before, peak_before = t, peak
    setup.add("to the run", time.perf_counter() - t_before)
    arms, weights_host = harness.build_arms(cell, args.seed, out_dir,
                                            bool(args.trace), parts=setup)
    t_built = time.perf_counter()
    for name, arm in arms.items():
        with setup(f"{name} first steps"):
            harness.first_steps(arm, config)
        say(f"{name} first steps: losses "
            f"{[round(x, 6) for x in arm.first['losses']]}")
    t_first = time.perf_counter()
    for name, arm in arms.items():
        with setup(f"{name} warm-up"):
            rec = harness.warm_up(arm, mix)
        f = arm.first
        say(f"{name} warm after {f['warm_intervals']} log interval(s): step "
            f"{f['warm_step_ms']:.3f} ms, {arm.steps_per_block} steps "
            f"to a block, num_selected {rec.get('num_selected')}, loss "
            f"{rec.get('loss')}")
    setup_s = time.perf_counter() - T_START
    compile_s, saved_s = compile_log.spent, compile_log.saved
    say(f"set-up {setup_s:.3f}s: to trainers {t_built - T_START:.1f}s, first "
        f"steps {t_first - t_built:.1f}s, warm-up "
        f"{time.perf_counter() - t_first:.1f}s; compile seconds spent "
        f"{compile_s:.1f}, saved by the cache {saved_s:.1f}, cache events "
        f"{compile_log.events}")
    say(setup.line("set-up by part:", setup_s))

    trace_dir = os.path.join(out_dir, "trace") if args.trace else None
    window = harness.measure(arms, mix, args.seconds, compile_log, trace_dir)
    device = harness.device_report(cell["chips"], arms)
    harness.say_memory(device)
    totals = {name: harness.arm_totals(arm) for name, arm in arms.items()}
    e2e = harness.end_to_end(arms, setup_s)
    sp = totals["sparse"]
    say(f"window {window['window_s']:.3f}s: "
        + ", ".join(f"{n} {t['steps']} steps ({t['skipped']} skipped) in "
                    f"{t['wall_s']:.3f}s" for n, t in totals.items())
        + f"; step_ms_p95 over {len(sp['iter_s'])} sparse iterations (median "
        f"{1e3 * harness.percentile(sp['iter_s'], 50):.3f} ms)"
        + (f"; sparse:dense "
           f"{e2e['examples_per_s'] / e2e['dense_examples_per_s']:.4f}"
           if e2e.get("dense_examples_per_s") else "")
        + f"; compilations inside the window {compile_log.in_window}")

    run = {"cell": cell, "config": config, "mix": mix, "peaks": peaks,
           "totals": totals, "setup_s": setup_s, "compile_s": compile_s,
           "compile_saved_s": saved_s, "device": device,
           "global_batch": {n: a.global_batch for n, a in arms.items()},
           "ef_numel": arms["sparse"].trainer.ts.ef_numel,
           "num_params": arms["sparse"].trainer.plan.total_numel,
           "k": arms["sparse"].trainer.plan.total_k,
           "blocks": {n: a.blocks for n, a in arms.items()},
           # {arm: [directory of each profiled block]}; empty untraced
           "trace_dirs": window["traced"],
           "log_every": int(mix["log_every"]), "trace": None}
    breakdown = None
    if args.trace:
        run["trace"] = trace_reduce.reduce_run(window["traced"], run)
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        breakdown = run["trace"]["breakdown"]
        for arm, a in run["trace"]["arms"].items():
            say(f"trace {arm}: {a['steps']} steps on {a['chips']} chip(s), "
                f"busy {a['busy_s']:.6f}s of {a['window_s']:.6f}s, per step "
                f"{1e3 * a['busy_s_per_step']:.3f} ms, custom calls "
                f"{a['kernels']}, collectives per step "
                f"{1e3 * a['collective_s_per_step']:.3f} ms, idle by host "
                f"phase {a['idle_named']}")
            for hlo in a["kernel_hlo"]:
                say(f"trace {arm} kernel: {hlo[:700]}")

    firsts = {n: types.SimpleNamespace(name=n, first=a.first)
              for n, a in arms.items()}
    window_info = {
        "compiles_in_window": compile_log.in_window,
        "failed_steps": sum(t["skipped"] for t in totals.values())}
    harness.close_arms(arms)
    checked = harness.Parts(compile_log)
    ok, numbers, lines, secs = check.run_check(
        cell, args.seed, firsts, weights_host, window_info,
        memory=device["memory"], parts=checked)
    harness.say_check_memory(device)
    say(f"the reference and the comparison took {secs:.1f}s (not in setup_s)")
    say(checked.line("check by part:", secs))
    device["seconds"] = {"set_up": setup.record(setup_s),
                         "check": checked.record(secs)}

    if args.trace:
        metrics = {}
        for m in cell["per_layer"]:
            reader = harness.load_layer_metric(cell["metrics_dir"], m["name"])
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    result = {"correct": bool(ok),
              "attempted": int(sum(t["steps"] for t in totals.values())),
              "failed": int(window_info["failed_steps"]),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    # each number compared beside its limit: the run's last lines on
    # standard error, and last in the result line
    result["check"] = check.as_record(numbers, config["limits"])
    for line in lines:
        say(line)
        print(line, file=sys.stderr, flush=True)
    say(f"to the result line {time.perf_counter() - T_START:.1f}s from "
        f"process start; the host's peak resident size "
        f"{harness.host_peak_gib():.2f} GiB")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
