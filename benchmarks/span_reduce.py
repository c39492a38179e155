"""From the program's own spans and scopes to numbers.

The program (`gaussiank_sgd_tpu`, from PR 24) names its work where it
happens. On the host, `Trainer` times every loop iteration as one
`iteration` span with leaf children (`data_wait`, `h2d`, `step_dispatch`,
`step_sync`, `step_readback`, `log_step`) and its construction as
`construct` with `build_data`, `build_model`, `build_step`, all on
`time.perf_counter_ns`, the clock of the harness's block times; the
finished spans stay in the process under `telemetry.tracing.recorded(run_id)`
after the trainers are freed. On the device, `jax.named_scope` puts a phase
name (`SCOPES`) into every operation's `op_name`, which the profiler writes
into the `tf_op` stat of the operation's EVENT METADATA in the `.xplane.pb`.
`jax.profiler.ProfileData` does not hand out the metadata's stats, so this
module decodes the file's protobuf wire format itself (the few message
types it needs, no dependency).

What this reduces them to, for the readers under `layer_metrics/`:

  host      per counted sparse block of a `--trace 1` run (the spans need
            no profiler): mean duration of each leaf span per iteration,
            `log_step` per occurrence, the mean of `data_wait.ready`, and
            the iteration's self time (its duration less its children's)
  set-up    `build_data` and `build_model + build_step`, both trainers
  device    per step of the profiled sparse block, averaged over the
            chips: each operation's SELF time (its duration less the
            operations nested in it) under the scope its `tf_op` names,
            and under none of them
  idle      the device-idle time of the profiled sparse block, on the
            host's clock, under each leaf span and under none

Every line of a trace, host or device, counts picoseconds from the moment
the profiler session started, which the file records as
`profile_start_time` on the plane "Task Environment" in unix nanoseconds; a
span's `perf_counter_ns` maps to unix nanoseconds through the recording's
clock pair. With host tracing on that places a span's `TraceAnnotation`
within 3 us of the span, and the device's step programs between their
dispatch and the end of their sync. In the benchmark's device-only trace the
device plane sits early by that mapping (a program would start before its
dispatch opens): by 0.13 ms at least, 0.85 ms at most in the cells measured.
So each profiled block is anchored on its own steps (`anchor_clock`): the
plane is moved as late as its steps allow, and what is left of the error is
the return of `block_until_ready` (PERF.md section 6, PR 24).

Everything returns None where the program has no such span or scope (a
parent commit without them), and nothing raises for that.
"""

from __future__ import annotations

import re
import struct
from typing import Any, Dict, Iterator, List, Optional, Tuple

from benchmarks import harness
from benchmarks.trace_reduce import (DEVICE_PLANE_PREFIX, MODULES_LINE,
                                     OPS_LINE, find_xplanes, opcode)

# the device phases the step programs name (parallel/trainstep.py, ops/)
SCOPES = ("fwd_bwd", "flatten", "ef_select", "cand_topk", "pack", "exchange",
          "scatter", "update", "guard", "step_metrics")
TASK_PLANE = "Task Environment"
START_STAT = "profile_start_time"


# ----------------------------------------------------- protobuf wire format

def _varint(b: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b: bytes) -> Iterator[Tuple[int, Any]]:
    """(field number, value) of one message: an int for a varint, the
    bytes for a length-delimited or fixed-width field."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v = b[i:i + size]
            i += size
        elif wire == 1:
            v = b[i:i + 8]
            i += 8
        elif wire == 5:
            v = b[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an .xplane.pb")
        yield key >> 3, v


def _stat(b: bytes, stat_names: Dict[int, str]) -> Tuple[str, Any]:
    """One XStat as (name, value)."""
    name, value = "", None
    for f, v in _fields(b):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f in (3, 4):
            value = v
        elif f == 5:
            value = v.decode("utf-8", "replace")
        elif f == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _map_entry(b: bytes) -> Tuple[int, bytes]:
    key, value = 0, b""
    for f, v in _fields(b):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def read_xspace(path: str,
                lines: Optional[Tuple[str, ...]] = (OPS_LINE, MODULES_LINE),
                ) -> List[dict]:
    """The planes of one `.xplane.pb`:
    `{"name", "stats": {name: value}, "lines": {line name: [event]}}` with
    an event `(name, start_ps, dur_ps, tf_op)`. `start_ps` counts from the
    line's `timestamp_ns`; `tf_op` is the event metadata's stat of that
    name ("" where it has none). Only the named lines are decoded (all of
    them with `lines=None`)."""
    with open(path, "rb") as f:
        data = f.read()
    planes = []
    for f1, pb in _fields(data):
        if f1 != 1:
            continue
        name, raw_lines, raw_meta, raw_stats = "", [], [], []
        stat_names: Dict[int, str] = {}
        for f2, v in _fields(pb):
            if f2 == 2:
                name = v.decode("utf-8", "replace")
            elif f2 == 3:
                raw_lines.append(v)
            elif f2 == 4:
                raw_meta.append(v)
            elif f2 == 5:
                key, meta = _map_entry(v)
                for f3, v3 in _fields(meta):
                    if f3 == 2:
                        stat_names[key] = v3.decode("utf-8", "replace")
            elif f2 == 6:
                raw_stats.append(v)
        meta_by_id: Dict[int, Tuple[str, str]] = {}
        for raw in raw_meta:
            key, meta = _map_entry(raw)
            ev_name, tf_op = "", ""
            for f3, v3 in _fields(meta):
                if f3 == 2:
                    ev_name = v3.decode("utf-8", "replace")
                elif f3 == 5:
                    sname, sval = _stat(v3, stat_names)
                    if sname == "tf_op" and isinstance(sval, str):
                        tf_op = sval
            meta_by_id[key] = (ev_name, tf_op)
        got_lines: Dict[str, list] = {}
        for raw in raw_lines:
            lname, t0_ns, raw_events = "", 0, []
            for f3, v3 in _fields(raw):
                if f3 == 2:
                    lname = v3.decode("utf-8", "replace")
                elif f3 == 3:
                    t0_ns = v3
                elif f3 == 4:
                    raw_events.append(v3)
            if lines is not None and lname not in lines:
                continue
            events = got_lines.setdefault(lname, [])
            for raw_ev in raw_events:
                mid = off = dur = 0
                for f4, v4 in _fields(raw_ev):
                    if f4 == 1:
                        mid = v4
                    elif f4 == 2:
                        off = v4
                    elif f4 == 3:
                        dur = v4
                ev_name, tf_op = meta_by_id.get(mid, (str(mid), ""))
                events.append((ev_name, t0_ns * 1000 + off, dur, tf_op))
        planes.append({"name": name, "lines": got_lines,
                       "stats": dict(_stat(s, stat_names)
                                     for s in raw_stats)})
    return planes


# ------------------------------------------------------------------ device

_NAME_PART = re.compile(r"[/()]")


def scope_of(tf_op: str) -> Optional[str]:
    """The phase an operation's `op_name` puts it under: `fwd_bwd` for
    everything inside it (a model's own module names may be any word),
    else the innermost of `SCOPES` on the path (a transformation wraps a
    name, `vmap(cand_topk)`), else None."""
    parts = _NAME_PART.split(tf_op)
    if "fwd_bwd" in parts:
        return "fwd_bwd"
    for part in reversed(parts):
        if part in SCOPES:
            return part
    return None


def self_times(ops: List[tuple]) -> List[Tuple[tuple, int]]:
    """(operation, self picoseconds): an operation's duration less the
    operations that run inside it (a `while` and its body)."""
    out: List[list] = []
    stack: List[list] = []          # [entry, end_ps]
    for op in sorted(ops, key=lambda o: (o[1], -o[2])):
        start, end = op[1], op[1] + op[2]
        while stack and start >= stack[-1][1]:
            stack.pop()
        if stack:
            stack[-1][0][1] -= min(end, stack[-1][1]) - start
        entry = [op, op[2]]
        out.append(entry)
        stack.append([entry, end])
    return [(op, max(0, ps)) for op, ps in out]


def reduce_device(trace_dir: str, steps: int) -> Optional[dict]:
    """The profiled block's device side: per-step seconds under each
    scope and under none (the chips' mean; the latter also by XLA's
    opcode, `unscoped_top`), the busy intervals and the step programs of
    the first chip in picoseconds since the session started, and the unix
    nanosecond at which it started."""
    planes, start_unix_ns = [], None
    for path in find_xplanes(trace_dir):
        for p in read_xspace(path):
            if p["name"] == TASK_PLANE:
                start_unix_ns = p["stats"].get(START_STAT, start_unix_ns)
            elif (p["name"].startswith(DEVICE_PLANE_PREFIX)
                  and p["lines"].get(OPS_LINE)):
                planes.append(p)
    if not planes:
        return None
    n = float(len(planes))
    by_scope: Dict[str, float] = {}
    unscoped: Dict[str, float] = {}     # by XLA's opcode
    for p in planes:
        for (name, _, _, tf_op), ps in self_times(p["lines"][OPS_LINE]):
            scope = scope_of(tf_op)
            by_scope[scope or ""] = by_scope.get(scope or "", 0.0) + ps
            if scope is None:
                kind = opcode(name)
                unscoped[kind] = unscoped.get(kind, 0.0) + ps
    per_step = {k: v / 1e12 / n / steps for k, v in by_scope.items()}
    first = planes[0]["lines"]
    top = sorted(unscoped.items(), key=lambda kv: -kv[1])[:6]
    # the step programs, not the scalar programs of a log line
    mods = first.get(MODULES_LINE, [])
    longest = max((m[2] for m in mods), default=0)
    return {"chips": int(n), "scope_s_per_step": per_step,
            "scoped": any(k for k in by_scope),
            "unscoped_top": [[k, v / 1e12 / n / steps] for k, v in top],
            "busy_ps": merged((s, s + d) for _, s, d, _ in first[OPS_LINE]),
            "programs_ps": sorted((m[1], m[1] + m[2]) for m in mods
                                  if m[2] >= 0.1 * longest),
            "start_unix_ns": start_unix_ns}


def merged(intervals) -> List[Tuple[int, int]]:
    """The union of [start, end) intervals as sorted disjoint ones."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


# -------------------------------------------------------------------- host

def spans_of(run_id: str):
    """The program's recording for one of the run's trainers, or None
    where the program keeps none (before PR 24, or tracing off)."""
    try:
        from gaussiank_sgd_tpu.telemetry import tracing
    except ImportError:
        return None
    recorded = getattr(tracing, "recorded", None)
    return recorded(run_id) if recorded is not None else None


def in_block(spans, block: dict) -> list:
    """The spans that lie inside a block of the harness: both ends between
    its `t0` and `t1`, which are `perf_counter` seconds, the spans'
    clock."""
    lo, hi = block["t0"] * 1e9, block["t1"] * 1e9
    return [s for s in spans if s.t0_ns >= lo and s.t1_ns <= hi]


def reduce_host(spans, blocks: List[dict]) -> Optional[dict]:
    """Means over the iterations of the given blocks, in seconds."""
    mine = [s for b in blocks for s in in_block(spans, b)]
    iters = [s for s in mine if s.name == "iteration"]
    if not iters:
        return None
    ids = {s.span_id for s in iters}
    children = [s for s in mine if s.parent in ids]
    total: Dict[str, float] = {}
    count: Dict[str, int] = {}
    for s in children:
        total[s.name] = total.get(s.name, 0.0) + (s.t1_ns - s.t0_ns) / 1e9
        count[s.name] = count.get(s.name, 0) + 1
    n = len(iters)
    whole = sum(s.t1_ns - s.t0_ns for s in iters) / 1e9
    ready = [s.fields["ready"] for s in children
             if s.name == "data_wait" and s.fields.get("ready") is not None]
    return {"iterations": n,
            "per_iteration_s": {k: v / n for k, v in total.items()},
            "per_occurrence_s": {k: total[k] / count[k] for k in total},
            "iteration_s": whole / n,
            "self_s": (whole - sum(total.values())) / n,
            "ready_mean": sum(ready) / len(ready) if ready else None}


def reduce_setup(recordings: list) -> Optional[dict]:
    """Seconds of each construction span, summed over the trainers."""
    total: Dict[str, float] = {}
    for rec in recordings:
        roots = {s.span_id for s in rec.spans if s.name == "construct"}
        for s in rec.spans:
            if s.parent in roots:
                total[s.name] = total.get(s.name, 0.0) + (
                    s.t1_ns - s.t0_ns) / 1e9
    return total or None


def anchor_clock(programs_ns: List[Tuple[float, float]], spans,
                 to_ns) -> Optional[dict]:
    """Where the device's clock sits against the host's, from the block's
    own steps. `programs_ns` are the step programs in nanoseconds since
    the session started, `to_ns` maps a span's `perf_counter_ns` onto the
    same count. The k-th step program of the trace belongs to the k-th
    iteration; it cannot have started before its `step_dispatch` span
    opened nor ended after its `step_sync` span closed, so the nanoseconds
    to ADD to the device's times lie between `lo_ns` (the largest
    dispatch-open less program-start) and `hi_ns` (the smallest sync-close
    less program-end). The nominal mapping through `profile_start_time`
    is right where that interval holds 0. `shift_ns` is `hi_ns`: the
    latest the plane can sit. `block_until_ready` returns within a
    fraction of a millisecond of the program's end whatever the program,
    while a program may start long after its dispatch (it waits for its
    input), so the upper bound is the tight one and what is left of the
    error is that return, at its shortest over the block's steps. None
    where programs and iterations do not pair up."""
    iters = sorted((s for s in spans if s.name == "iteration"),
                   key=lambda s: s.t0_ns)
    if not iters or len(iters) != len(programs_ns):
        return None
    kids: Dict[str, Dict[str, Any]] = {}
    for s in spans:
        if s.name in ("step_dispatch", "step_sync"):
            kids.setdefault(s.parent, {})[s.name] = s
    lo, hi = [], []
    for it, (p0, p1) in zip(iters, programs_ns):
        mine = kids.get(it.span_id, {})
        if len(mine) != 2:
            return None
        lo.append(to_ns(mine["step_dispatch"].t0_ns) - p0)
        hi.append(to_ns(mine["step_sync"].t1_ns) - p1)
    before, after = [], []      # the device's wait inside each step_sync
    for it, (p0, p1) in zip(iters, programs_ns):
        sync = kids[it.span_id]["step_sync"]
        y0, y1 = to_ns(sync.t0_ns), to_ns(sync.t1_ns)
        before.append(max(0.0, min(p0 + min(hi), y1) - y0))
        after.append(max(0.0, y1 - max(p1 + min(hi), y0)))
    return {"lo_ns": max(lo), "hi_ns": min(hi), "shift_ns": min(hi),
            "lo_spread_ns": max(lo) - min(lo),
            "hi_spread_ns": max(hi) - min(hi),
            "sync_before_program_ns": sum(before) / len(before),
            "sync_after_program_ns": sum(after) / len(after)}


def name_idle(busy_ns: List[Tuple[float, float]], spans, to_ns,
              block: dict) -> dict:
    """The block's device-idle time by the leaf span the host was in:
    seconds under each leaf's name, and under `unnamed` where no leaf
    span was open. Idle is the block's wall interval less the busy
    intervals; `busy_ns` counts nanoseconds since the session started,
    and `to_ns` maps `perf_counter_ns` onto the same count."""
    lo = to_ns(int(block["t0"] * 1e9))
    hi = to_ns(int(block["t1"] * 1e9))
    idle, at = [], lo
    for s, e in busy_ns:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > at:
            idle.append((at, s))
        at = max(at, e)
    if hi > at:
        idle.append((at, hi))
    parents = {s.parent for s in spans}
    leaves = sorted((to_ns(s.t0_ns), to_ns(s.t1_ns), s.name)
                    for s in in_block(spans, block)
                    if s.span_id not in parents)
    named: Dict[str, float] = {}
    covered = 0.0
    i = 0
    for a, b in idle:
        while i < len(leaves) and leaves[i][1] <= a:
            i += 1
        j = i
        while j < len(leaves) and leaves[j][0] < b:
            s, e, name = leaves[j]
            part = min(e, b) - max(s, a)
            if part > 0:
                named[name] = named.get(name, 0.0) + part / 1e9
                covered += part
            j += 1
    total = sum(b - a for a, b in idle)
    named["unnamed"] = max(0.0, (total - covered) / 1e9)
    return {"idle_s": total / 1e9, "by_span_s": named}


# ------------------------------------------------------------- the one run

def reduced(run: dict) -> Optional[dict]:
    """Everything above for one run of the benchmark, computed once and
    kept in `run`; its lines for people are printed as it is made. None
    where the program recorded no spans."""
    if "span_reduce" in run:
        return run["span_reduce"]
    run["span_reduce"] = out = _reduce_run(run)
    return out


def _reduce_run(run: dict) -> Optional[dict]:
    say = harness.say
    sparse = spans_of("sparse")
    if sparse is None or not sparse.spans:
        return None
    spans = list(sparse.spans)
    blocks = run["blocks"]["sparse"]
    host = reduce_host(spans, blocks)
    if host is None:        # a recording, but not of this run's blocks
        return None
    others = [spans_of(arm) for arm in run["blocks"] if arm != "sparse"]
    out: Dict[str, Any] = {
        "host": host,
        "setup": reduce_setup([sparse] + [r for r in others
                                          if r is not None]),
        "device": None, "clock": None, "idle": None}
    say(f"spans sparse: {host['iterations']} iterations of "
        f"{1e3 * host['iteration_s']:.3f} ms; per iteration "
        + ", ".join(f"{k} {1e3 * v:.3f}" for k, v in sorted(
            host["per_iteration_s"].items(), key=lambda kv: -kv[1]))
        + f", self {1e3 * host['self_s']:.3f} ms; data_wait.ready mean "
        f"{host['ready_mean']}")
    if out["setup"]:
        say("spans construction, all trainers: " + ", ".join(
            f"{k} {v:.3f} s" for k, v in sorted(out["setup"].items())))
    # the profiled blocks' directories are the run's own (`trace_dirs`)
    dirs = run.get("trace_dirs") or {}
    traced = [b for b in blocks if b.get("traced")]
    if not traced or not run.get("trace") or not dirs.get("sparse"):
        return out
    block = traced[0]
    dev = reduce_device(dirs["sparse"][0], block["steps"])
    if dev is None:
        return out
    out["device"] = dev
    _say_scopes("sparse", dev)
    # for people: no metric reads the dense arm's scopes
    dense = [b for b in run["blocks"].get("dense", []) if b.get("traced")]
    if dense and dirs.get("dense"):
        _say_scopes("dense", reduce_device(dirs["dense"][0],
                                           dense[0]["steps"]))
    if dev["start_unix_ns"] is not None:
        start = dev["start_unix_ns"]

        def to_ns(perf_ns):     # since the session started, in whole ns
            return sparse.wall_ns(perf_ns) - start

        out["clock"] = clock = anchor_clock(
            [(s / 1e3, e / 1e3) for s, e in dev["programs_ps"]],
            in_block(spans, block), to_ns)
        shift = 0.0
        if clock is None:
            say(f"clock sparse: {len(dev['programs_ps'])} step programs in "
                f"the trace do not pair with the block's iterations; the "
                f"device plane is placed by profile_start_time alone")
        else:
            shift = clock["shift_ns"]
            say(f"clock sparse: the device plane counts from "
                f"profile_start_time; against the block's own steps it "
                f"has to move by {clock['lo_ns'] / 1e3:.1f} to "
                f"{clock['hi_ns'] / 1e3:.1f} us (a program starts after "
                f"its dispatch opens and ends before its sync closes; "
                f"over the steps these bounds spread "
                f"{clock['lo_spread_ns'] / 1e3:.1f} and "
                f"{clock['hi_spread_ns'] / 1e3:.1f} us); moved by "
                f"{shift / 1e3:.1f} us, the latest it can sit. Then of "
                f"each step_sync span "
                f"{clock['sync_before_program_ns'] / 1e6:.3f} ms pass "
                f"before the step program starts (it waits for its "
                f"input) and {clock['sync_after_program_ns'] / 1e6:.3f} "
                f"ms after it ends")
        busy = [(shift + s / 1e3, shift + e / 1e3)
                for s, e in dev["busy_ps"]]
        out["idle"] = idle = name_idle(busy, spans, to_ns, block)
        say(f"idle sparse: {idle['idle_s']:.6f} s of the profiled block's "
            f"{block['t1'] - block['t0']:.6f} s, by the host's leaf span: "
            + ", ".join(f"{k} {v:.6f}" for k, v in sorted(
                idle["by_span_s"].items(), key=lambda kv: -kv[1])))
    return out


def _say_scopes(arm: str, dev: Optional[dict]) -> None:
    if dev is None:
        return
    per = dev["scope_s_per_step"]
    total = sum(per.values())
    harness.say(
        f"scopes {arm}, ms per step on {dev['chips']} chip(s): "
        + ", ".join(f"{k} {1e3 * per[k]:.3f}" for k in SCOPES if k in per)
        + f"; under none of them {1e3 * per.get('', 0.0):.3f} ms "
        f"({100.0 * per.get('', 0.0) / max(total, 1e-12):.2f} % of the "
        f"operations' time), by XLA's opcode: "
        + ", ".join(f"{k} {1e3 * v:.3f}" for k, v in dev["unscoped_top"]))


def host_ms(run: dict, name: str, per: str = "per_iteration_s",
            ) -> Optional[float]:
    """A leaf span's mean milliseconds, for the readers."""
    r = reduced(run)
    if not r or not r["host"] or name not in r["host"][per]:
        return None
    return 1e3 * r["host"][per][name]


def scope_ms(run: dict, *names: str) -> Optional[float]:
    """Milliseconds per step under the named scopes together; None where
    the trace carries no scope at all or none of these."""
    r = reduced(run)
    if not r or not r["device"] or not r["device"]["scoped"]:
        return None
    per = r["device"]["scope_s_per_step"]
    if not any(n in per for n in names):
        return None
    return 1e3 * sum(per.get(n, 0.0) for n in names)
