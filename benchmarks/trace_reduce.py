"""From a profiler trace (`.xplane.pb`) to numbers.

The traced run wraps one steady block of each trainer in
`jax.profiler.start_trace/stop_trace`; this module reads what that wrote with
`jax.profiler.ProfileData` and reduces it, per block, to:

  busy_s             union of the intervals in which an operation ran on a
                     device, averaged over the chips
  window_s           the block's wall time by the host's clock (the profiler
                     starts before it and stops after it)
  kernel_s           device time of the step's `tpu_custom_call`s (the fused
                     EF+select Mosaic kernel), averaged over the chips
  collective_s       device time of cross-chip collectives, averaged
  top device ops     by XLA's names, seconds summed over the block, per chip
  idle gaps          device-idle time between consecutive step programs,
                     named by what the host's loop was doing then (from the
                     timed iterator: `data_wait`; every `log_every`-th
                     iteration: `log_step`; else `loop_other`), and the idle
                     time inside step programs (`in_step`)

Names are XLA's own: the package has no `jax.named_scope` and the Mosaic
`pallas_call` no `name=` yet (PERF.md, open questions). On a TPU plane the
line "XLA Ops" holds one event per executed HLO operation and "XLA Modules"
one per executed program.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE_PREFIX = "/device:TPU:"
COLLECTIVE_MARKS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute")
KERNEL_TARGET = "tpu_custom_call"

Interval = Tuple[int, int]

_OPCODE = re.compile(r"= .*? ([a-z][a-z0-9\-]*)\(")


def short_name(name: str) -> str:
    """XLA names a device event by its whole HLO line, `%fusion.3 = f32[...]
    fusion(...)`: keep the operation's name and its opcode."""
    head = name.split(" = ", 1)[0].lstrip("%")
    m = _OPCODE.search(name)
    return f"{head} {m.group(1)}" if m and m.group(1) not in head else head


def union_ns(intervals: Iterable[Interval]) -> int:
    """Total length of the union of [start, end) intervals."""
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def opcode(name: str) -> str:
    """The operation's own opcode (an operand's name may hold any word)."""
    m = _OPCODE.search(name)
    return m.group(1) if m else name.split(" = ", 1)[0].lstrip("%")


def is_collective(name: str) -> bool:
    return any(opcode(name).startswith(m) for m in COLLECTIVE_MARKS)


def is_kernel(name: str) -> bool:
    """A Mosaic kernel: a `custom-call` whose target is `tpu_custom_call`.
    (The trace cuts long HLO lines, so where the target is cut off, a
    custom call that takes real time counts; XLA's own bookkeeping calls
    take nanoseconds.)"""
    return opcode(name) == "custom-call" and (
        KERNEL_TARGET in name or "custom_call_target" not in name)


_ARRAY = re.compile(r"[a-z]+[0-9]+\[([0-9,]*)\]\{([^}]*)\}")


def hbm_passes(kernel_hlo: str) -> Tuple[int, int]:
    """(passes, elements): how many of the kernel's largest arrays, results
    and operands alike, live in HBM, by the layouts in its HLO line. XLA
    writes `S(n)` into the layout of an array that it keeps in another
    memory space than HBM; an array without it is read from or written to
    HBM inside the kernel's time."""
    head = kernel_hlo.split("custom_call_target", 1)[0]
    arrays = []
    for dims, layout in _ARRAY.findall(head):
        numel = 1
        for d in filter(None, dims.split(",")):
            numel *= int(d)
        arrays.append((numel, "S(" not in layout))
    if not arrays:
        return 0, 0
    largest = max(n for n, _ in arrays)
    return sum(1 for n, in_hbm in arrays if n == largest and in_hbm), largest


def read_planes(path: str, plane_prefix: str = DEVICE_PLANE_PREFIX) -> list:
    """[{"name", "ops": [(name, start_ns, dur_ns)], "modules": [...]}] for
    every device plane of one `.xplane.pb`."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        if not plane.name.startswith(plane_prefix):
            continue
        got = {"name": plane.name, "ops": [], "modules": []}
        for line in plane.lines:
            if line.name == OPS_LINE:
                key = "ops"
            elif line.name == MODULES_LINE:
                key = "modules"
            else:
                continue
            for ev in line.events:
                got[key].append((ev.name, int(ev.start_ns),
                                 int(ev.duration_ns)))
        if got["ops"]:
            planes.append(got)
    return planes


def reduce_plane(plane: dict) -> dict:
    """One device's numbers, in seconds."""
    ops = plane["ops"]
    busy = union_ns((s, s + d) for _, s, d in ops)
    kernel = sum(d for n, _, d in ops if is_kernel(n) and d > 100)
    kernel_hlo = sorted({n for n, _, d in ops if is_kernel(n) and d > 100})
    coll = sum(d for n, _, d in ops if is_collective(n))
    by_name: Dict[str, int] = {}
    for n, _, d in ops:
        n = short_name(n)
        by_name[n] = by_name.get(n, 0) + d
    # the step programs, not the scalar conversions a log line dispatches
    longest = max((m[2] for m in plane["modules"]), default=0)
    mods = sorted((m for m in plane["modules"] if m[2] >= 0.1 * longest),
                  key=lambda m: m[1])
    gaps = [max(0, b[1] - (a[1] + a[2])) for a, b in zip(mods, mods[1:])]
    in_module = sum(m[2] for m in mods)
    # operations that ran inside a step program (the log line's scalar
    # programs run between them)
    starts = [m[1] for m in mods]
    ends = [m[1] + m[2] for m in mods]

    def in_a_step(s):
        i = bisect.bisect_right(starts, s) - 1
        return i >= 0 and s < ends[i]

    inside = union_ns((s, s + d) for _, s, d in ops if in_a_step(s))
    return {"busy_s": busy / 1e9, "kernel_s": kernel / 1e9,
            "collective_s": coll / 1e9, "kernel_hlo": kernel_hlo,
            "by_name": {n: d / 1e9 for n, d in by_name.items()},
            "modules": len(mods), "module_s": in_module / 1e9,
            "gaps_s": [g / 1e9 for g in gaps],
            "in_step_idle_s": max(0.0, (in_module - inside) / 1e9)}


def profiler_options():
    """Device tracing only: the host's and Python's tracers make a trace of
    a few steps tens of megabytes and slow the loop they watch."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 0
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def find_xplanes(trace_dir: str) -> List[str]:
    return sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))


def reduce_block(trace_dir: str, block: dict, log_every: int,
                 plane_prefix: str = DEVICE_PLANE_PREFIX) -> dict:
    """One traced block: the chips' mean of each number, per step too, and
    the idle gaps named from the block's own host timings."""
    paths = find_xplanes(trace_dir)
    if not paths:
        raise FileNotFoundError(f"the profiler wrote no .xplane.pb under "
                                f"{trace_dir}")
    planes = [reduce_plane(p) for path in paths
              for p in read_planes(path, plane_prefix)]
    if not planes:
        raise RuntimeError(f"no device plane with operations in {paths}: a "
                           f"traced block in which nothing ran on a device")
    n = float(len(planes))
    steps = block["steps"]
    busy = sum(p["busy_s"] for p in planes) / n
    kernel = sum(p["kernel_s"] for p in planes) / n
    coll = sum(p["collective_s"] for p in planes) / n
    by_name: Dict[str, float] = {}
    for p in planes:
        for name, s in p["by_name"].items():
            by_name[name] = by_name.get(name, 0.0) + s / n
    # idle gaps of the first chip, named by the host's loop: the gap after
    # the i-th step program is the loop's work between two dispatches
    first = planes[0]
    named = {"data_wait": 0.0, "log_step": 0.0, "loop_other": 0.0,
             "in_step": first["in_step_idle_s"]}
    waits = block["wait_s"]
    for i, gap in enumerate(first["gaps_s"]):
        wait = waits[i + 1] if i + 1 < len(waits) else 0.0
        part = min(gap, wait)
        named["data_wait"] += part
        done = block["first_step"] + i + 1      # steps finished before it
        key = "log_step" if done % log_every == 0 else "loop_other"
        named[key] += gap - part
    kernels = {k: v for k, v in by_name.items()
               if k.endswith(" custom-call") and v > 1e-6}
    return {"steps": steps, "chips": int(n), "busy_s": busy,
            "kernels": kernels, "kernel_hlo": first["kernel_hlo"],
            "window_s": block["t1"] - block["t0"],
            "busy_s_per_step": busy / steps,
            "kernel_s_per_step": kernel / steps,
            "collective_s_per_step": coll / steps,
            "modules_per_chip": first["modules"],
            "by_name": by_name, "idle_named": named}


def reduce_run(traced: Dict[str, List[str]], run: dict) -> dict:
    """All of a traced run's blocks. `device.busy_s` and `window_s` of the
    result line are the sparse trainer's traced block (the system under
    test); the dense baseline's block feeds its own layer metrics."""
    arms = {}
    for arm, dirs in traced.items():
        blocks = [b for b in run["blocks"][arm] if b["traced"]]
        if not dirs or not blocks:
            continue
        arms[arm] = reduce_block(dirs[0], blocks[0], run["log_every"])
    main = arms.get("sparse") or next(iter(arms.values()))
    ops = sorted(main["by_name"].items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(main["idle_named"].items(), key=lambda kv: -kv[1])[:10]
    return {"arms": arms, "busy_s": main["busy_s"],
            "window_s": main["window_s"],
            "breakdown": {"device_ops": [[n, s] for n, s in ops],
                          "idle_gaps": [[n, s] for n, s in gaps]}}
