"""What a model names inside `fwd_bwd`, and its counters (PR 31).

`span_reduce` puts every operation under `fwd_bwd` into that one scope. A
model may name its own parts there (`jax.named_scope`), and a step that
calls Mosaic kernels of its own shows them as `tpu_custom_call`s beside the
EF+select one. Which names are the model's is the CONFIGURATION's to say
(`configs/<name>.json`): `model_scopes`, the scopes, and
`kernels_without_scope`, {kernel: scope} for the kernels that the compiler
emits with no `op_name` (XLA's own `ragged-dot` kernels) and the scope
whose work they do. A configuration without the keys names nothing. This
module reads both from the profiled sparse block of a `--trace 1` run, with
`span_reduce`'s decoder and its self times:

  scope_s_per_step   operations' self time under the innermost model scope
                     on their `op_name` path (a transformation wraps a name:
                     `transpose(jvp(attn_full))`), and of the kernels
                     without a scope that belong to it, the chips' mean
  kernels            per Mosaic kernel, by the name before the first `.`
                     of its HLO line (`splash_mqa_fwd_residuals`,
                     `ragged-dot-none`, ...): seconds and calls per step

and the model's counters from the sparse trainer's own `train` records
(`<run's output directory>/sparse/metrics.jsonl`; the loss function's
auxiliary output, read once per `log_every`), over the counted blocks.

Everything returns None where the program names no such scope, kernel or
counter (a parent commit, another model), and nothing raises for that.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Mapping, Optional, Sequence

from benchmarks import span_reduce
from benchmarks.trace_reduce import (DEVICE_PLANE_PREFIX, OPS_LINE,
                                     find_xplanes, is_kernel)


def scope_of(tf_op: str, scopes: Sequence[str]) -> Optional[str]:
    """The innermost of `scopes` on an operation's `op_name` path."""
    for part in reversed(span_reduce._NAME_PART.split(tf_op)):
        if part in scopes:
            return part
    return None


def kernel_name(hlo_line: str) -> str:
    """`%splash_mqa_fwd_residuals.3 = (...) custom-call(...)` ->
    `splash_mqa_fwd_residuals`."""
    head = hlo_line.split(" = ", 1)[0].lstrip("%")
    stem, _, last = head.rpartition(".")
    return stem if stem and last.isdigit() else head


def reduce_device(trace_dir: str, steps: int, scopes: Sequence[str] = (),
                  unscoped: Optional[Mapping[str, str]] = None
                  ) -> Optional[dict]:
    """`scopes`: the configuration's `model_scopes`; `unscoped`: its
    `kernels_without_scope`."""
    unscoped = unscoped or {}
    planes = [p for path in find_xplanes(trace_dir)
              for p in span_reduce.read_xspace(path)
              if p["name"].startswith(DEVICE_PLANE_PREFIX)
              and p["lines"].get(OPS_LINE)]
    if not planes:
        return None
    per = float(len(planes)) * steps
    by_scope: Dict[str, float] = {}
    kernels: Dict[str, Dict[str, float]] = {}
    for p in planes:
        for (name, _, dur, tf_op), ps in span_reduce.self_times(
                p["lines"][OPS_LINE]):
            scope = scope_of(tf_op, scopes)
            if scope is None and is_kernel(name):
                scope = unscoped.get(kernel_name(name))
            if scope:
                by_scope[scope] = by_scope.get(scope, 0.0) + ps / 1e12 / per
            if is_kernel(name) and dur > 100_000:       # over 100 ns
                k = kernels.setdefault(kernel_name(name),
                                       {"s_per_step": 0.0,
                                        "calls_per_step": 0.0})
                k["s_per_step"] += dur / 1e12 / per
                k["calls_per_step"] += 1.0 / per
    return {"scope_s_per_step": by_scope, "kernels": kernels}


def reduced(run: dict) -> Optional[dict]:
    """The profiled sparse block's reading, made once a run and kept in
    `run`; its line for people is printed as it is made."""
    if "model_scopes" in run:
        return run["model_scopes"]
    run["model_scopes"] = out = None
    dirs = (run.get("trace_dirs") or {}).get("sparse")
    traced = [b for b in run["blocks"]["sparse"] if b.get("traced")]
    if not dirs or not traced or not run.get("trace"):
        return None
    config = run["config"]
    run["model_scopes"] = out = reduce_device(
        dirs[0], traced[0]["steps"], config.get("model_scopes", ()),
        config.get("kernels_without_scope"))
    if out and (out["scope_s_per_step"] or out["kernels"]):
        span_reduce.harness.say(
            "model scopes sparse, ms per step: "
            + ", ".join(f"{k} {1e3 * v:.3f}" for k, v in sorted(
                out["scope_s_per_step"].items(), key=lambda kv: -kv[1]))
            + "; kernels, ms per step (calls): "
            + ", ".join(f"{k} {1e3 * v['s_per_step']:.3f} "
                        f"({v['calls_per_step']:g})"
                        for k, v in sorted(
                            out["kernels"].items(),
                            key=lambda kv: -kv[1]["s_per_step"])))
    return out


def scope_ms(run: dict, name: str) -> Optional[float]:
    r = reduced(run)
    if not r or name not in r["scope_s_per_step"]:
        return None
    return 1e3 * r["scope_s_per_step"][name]


def kernel(run: dict, name: str) -> Optional[dict]:
    """{"s_per_step", "calls_per_step"} of the kernel of that name."""
    r = reduced(run)
    return r["kernels"].get(name) if r else None


def train_records(run: dict) -> List[dict]:
    """The sparse trainer's `train` records of the counted blocks' steps,
    read once a run."""
    if "train_records" in run:
        return run["train_records"]
    run["train_records"] = out = []
    dirs = (run.get("trace_dirs") or {}).get("sparse")
    if not dirs:
        return out
    out_dir = os.path.dirname(os.path.dirname(dirs[0]))
    path = os.path.join(out_dir, "sparse", "metrics.jsonl")
    if not os.path.exists(path):
        return out
    counted = [(b["first_step"], b["first_step"] + b["steps"])
               for b in run["blocks"]["sparse"]]
    with open(path) as f:
        for line in f:
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if rec.get("event") == "train" and any(
                    lo < rec.get("step", -1) <= hi for lo, hi in counted):
                out.append(rec)
    return out


def counter(run: dict, name: str) -> Optional[float]:
    """Mean of a counter over the counted blocks' `train` records."""
    values = [r[name] for r in train_records(run) if name in r]
    return sum(values) / len(values) if values else None
