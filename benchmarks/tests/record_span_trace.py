#!/usr/bin/env python3
"""Record the small trace WITH the program's scopes and spans that
`test_span_reduce.py` checks `span_reduce.py` against, and take the clock
study PERF.md reports (PR 24). Run on a machine with a TPU:

    python3 benchmarks/tests/record_span_trace.py chiprun_out/testdata_spans

Like `record_trace.py` it builds the tests' tiny throw-away cell, but with
the program's tracing on, takes the sparse trainer through its warm-up and
profiles one block of four steps with the benchmark's own profiler options
(device events only). It copies out the trace (as `.xspace.pb`), the block's host
timings and the program's recorded spans (the construction's and the
block's, with the recording's clock pairs).

Then the clock study, twice: on that device-only trace, and on a second
window of four steps profiled by the program's own `--profile-steps`
mechanism (`telemetry.profiler.ProfilerSession`, host tracing on), whose
file also holds the `TraceAnnotation` every span opens. It prints, in
microseconds: annotation start less the same span's recorded start mapped
to the wall clock; and each step program's start on the device plane less
its `step_dispatch` span's start, and its `step_sync` span's end less the
program's end, with the device plane's picoseconds counted from the
session's `profile_start_time`.
"""

import json
import os
import shutil
import statistics
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

NAME = "tiny_spans_4steps"


def spread(values):
    values = sorted(values)
    if not values:
        return None
    return {"n": len(values), "min": values[0],
            "median": statistics.median(values), "max": values[-1]}


def device_against_spans(path, mine, recording):
    """The step programs of the first device plane against the spans
    `mine` of the iterations that dispatched them
    (`span_reduce.anchor_clock`), microseconds: by how much the device
    plane has to move."""
    from benchmarks import span_reduce as sr
    dev = sr.reduce_device(os.path.dirname(path), 1)
    t0 = dev["start_unix_ns"]
    clock = sr.anchor_clock(
        [(a / 1e3, b / 1e3) for a, b in dev["programs_ps"]], mine,
        lambda perf_ns: recording.wall_ns(perf_ns) - t0)
    return {"profile_start_time": t0, "programs": len(dev["programs_ps"]),
            "clock_us": clock and {k: v / 1e3 for k, v in clock.items()}}


def annotations_against_spans(path, spans, recording):
    """Every `TraceAnnotation` of a span's name on the host planes against
    the recorded span nearest to it, microseconds."""
    from benchmarks import span_reduce as sr
    names = {s.name for s in spans}
    notes, start_ns = [], 0
    planes = sr.read_xspace(path, lines=None)
    for p in planes:
        if p["name"] == sr.TASK_PLANE:
            start_ns = p["stats"].get(sr.START_STAT, 0)
    for p in planes:
        if not p["name"].startswith("/host:"):
            continue
        for events in p["lines"].values():
            # a host line counts from the session's start too, unless its
            # own timestamp already is a unix time
            notes += [(n, ps / 1e3 + (start_ns if ps < 1e29 else 0), d / 1e3)
                      for n, ps, d, _ in events if n in names]
    diffs, durs, by_name = [], [], {}
    for name, t_ns, d_ns in notes:
        mine = [s for s in spans if s.name == name]
        s = min(mine, key=lambda s: abs(recording.wall_ns(s.t0_ns) - t_ns))
        diffs.append((t_ns - recording.wall_ns(s.t0_ns)) / 1e3)
        durs.append((d_ns - (s.t1_ns - s.t0_ns)) / 1e3)
        by_name[name] = by_name.get(name, 0) + 1
    return {"annotations": by_name,
            "annotation_start_less_span_start_us": spread(diffs),
            "annotation_duration_less_span_duration_us": spread(durs)}


def main(out: str) -> int:
    import jax
    if jax.default_backend() != "tpu":
        print("record_span_trace.py: needs a TPU", file=sys.stderr)
        return 2
    from tiny_root import write_tiny_root
    from benchmarks import harness, span_reduce as sr, trace_reduce
    from gaussiank_sgd_tpu.telemetry.profiler import ProfilerSession
    root = tempfile.mkdtemp(prefix="tiny_root_")
    write_tiny_root(root)
    cell = harness.load_cell("tiny_dp1", root=root)
    cell["config_data"]["states"]["kernel_mode"] = "mosaic"
    out_dir = harness.make_out_dir()
    os.makedirs(out, exist_ok=True)
    try:
        arms, _ = harness.build_arms(cell, 3, out_dir, True)
        arm = arms["sparse"]
        harness.warm_up(arm, cell["mix"])
        arm.steps_per_block = 4
        tdir = os.path.join(out_dir, "trace", "sparse_0")
        block = harness.run_block(arm, tdir)
        rec = sr.spans_of("sparse")
        spans = list(rec.spans)
        path = trace_reduce.find_xplanes(tdir)[0]
        # not `*.xplane.pb`: `test_trace_reduce.py` reduces every such file
        # under testdata/ as one trace
        shutil.copy(path, os.path.join(out, NAME + ".xspace.pb"))
        with open(os.path.join(out, NAME + ".block.json"), "w") as f:
            json.dump(block, f)
        roots = {s.span_id for s in spans if s.name == "construct"}
        keep = [s for s in spans if s.span_id in roots or s.parent in roots]
        keep += sr.in_block(spans, block)
        with open(os.path.join(out, NAME + ".spans.json"), "w") as f:
            json.dump({"run_id": rec.run_id, "trace_id": rec.trace_id,
                       "anchors": rec.anchors,
                       "spans": [list(s) for s in keep]}, f)
        study = {"device_only": device_against_spans(
            path, sr.in_block(spans, block), rec)}
        study["host"] = sr.reduce_host(spans, [block])
        dev = sr.reduce_device(tdir, block["steps"])
        if dev is not None and dev["start_unix_ns"] is not None:
            study["scopes_ms_per_step"] = {
                k or "none": 1e3 * v
                for k, v in dev["scope_s_per_step"].items()}
            study["unscoped_top"] = dev["unscoped_top"]
            t0 = dev["start_unix_ns"]
            shift = (study["device_only"]["clock_us"] or {}).get(
                "shift_ns", 0.0) * 1e3
            busy = [(shift + s / 1e3, shift + e / 1e3)
                    for s, e in dev["busy_ps"]]
            study["idle"] = sr.name_idle(
                busy, spans, lambda perf_ns: rec.wall_ns(perf_ns) - t0,
                block)
        kernels = sorted({n[:160] for p in sr.read_xspace(path)
                          for n, _, _, _ in p["lines"].get(sr.OPS_LINE, [])
                          if "custom-call" in n})
        study["custom_calls"] = kernels
        # the program's own window, host tracing on
        tr = arm.trainer
        step = tr._step_cache
        pdir = os.path.join(out_dir, "own_profile")
        tr.profiler = ProfilerSession(pdir, step + 1, step + 5, bus=tr.bus)
        arm.train(7)
        tr.profiler.close()
        own = trace_reduce.find_xplanes(pdir)[0]
        spans = list(rec.spans)
        # the window opens inside the iteration that takes step + 2
        its = {s.span_id for s in spans if s.name == "iteration"
               and step + 2 <= s.fields["step"] <= step + 5}
        profiled = [s for s in spans if s.span_id in its or s.parent in its]
        study["own_window"] = {
            "bytes": os.path.getsize(own),
            "planes": [[p["name"], {k: len(v) for k, v in p["lines"].items()}
                        ][:2] for p in sr.read_xspace(own, lines=None)][:12],
            **annotations_against_spans(own, spans, rec),
            **device_against_spans(own, profiled, rec)}
        print(json.dumps(study, indent=1, default=str))
        with open(os.path.join(out, NAME + ".study.json"), "w") as f:
            json.dump(study, f, indent=1, default=str)
        print("trace bytes", os.path.getsize(path))
        harness.close_arms(arms)
    finally:
        harness.remove_out_dir(out_dir)
        shutil.rmtree(root, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1
                  else "chiprun_out/testdata_spans"))
