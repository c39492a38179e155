"""The whole `op_name` path of every device operation (PR 36).

`span_reduce.scope_of` stops at `fwd_bwd` and `model_scopes.scope_of` keeps
the innermost name of a configuration's list; both throw away the rest of
what JAX writes into an operation's `op_name`:

    jit(sparse_step_fn)/fwd_bwd/transpose(jvp(Mellum2))/fwd_bwd/jvp(Mellum2)/
        checkpoint/rematted_computation/layers_1/attn/attn_proj/q_proj/dot_general:

the program's scopes outermost to innermost (`fwd_bwd`, `attn_proj`), the
pass (a transposition is a backward operation, `rematted_computation` a
forward one run again inside the backward pass), the flax modules
(`Mellum2/layers_1/attn/q_proj`) and the primitive. This module keeps them.
From the profiled sparse block of a `--trace 1` run, in ONE decode that
every reader shares (`reduced`, kept in `run`), with `span_reduce`'s decoder
and self times, the chips' mean per step:

  rows      {(chain, pass): seconds}: `chain` the known names on the path,
            outermost first. Its head is what `span_reduce.scope_of` says
            (so both readers agree on the step's phases by construction);
            under `fwd_bwd` the models' names follow (`MODEL_NAMES`: a name
            a model opens with `jax.named_scope`, whichever model), none
            twice. An operation under no phase has the empty chain: XLA's
            copies and layout changes, and the kernels the compiler emits
            with their own name for an `op_name` and no path
            (`ragged-dot-none:`), which stay where `model_scopes` puts
            them; their pass is not guessed.
  unnamed   {path tail: seconds} of the operations under `fwd_bwd` and no
            name of a model's, by the last three words of their path: what
            a next scope would have to enclose.
  modules   {flax module path: seconds} of the operations under `fwd_bwd`:
            the path's plain words that are neither a scope nor a wrapper
            nor inside a `jit(...)` (a jitted library function), without the
            primitive. They need no scope in the model.

Everything returns None where the run has no trace or the trace names no
such scope (a parent commit, another model's cell), and nothing raises.
"""

from __future__ import annotations

import re
import time
from typing import Dict, Optional, Sequence, Tuple

from benchmarks import model_scopes, span_reduce
from benchmarks.trace_reduce import (DEVICE_PLANE_PREFIX, OPS_LINE,
                                     find_xplanes, is_kernel)

# what `models/mellum2.py`, `models/joyai_flash.py` and the `lm` loss of
# `training/losses.py` open inside `fwd_bwd` (docs/OBSERVABILITY.md)
MODEL_NAMES = frozenset((
    "attn_window", "attn_full", "attn_proj", "rope", "rms_norm", "embed",
    "lm_head", "loss", "moe_router", "moe_route_sort", "moe_experts",
    "moe_to_rows", "moe_to_tokens", "moe_gate", "moe_product_glue",
    "moe_shared", "dense_mlp", "attn_mla", "mla_proj", "mla_q", "mla_kv",
    "mla_out", "mla_assemble", "mtp", "layer_scan"))
NAMES = frozenset(span_reduce.SCOPES) | MODEL_NAMES
# the words that control flow and `jax.checkpoint` put on the path (a
# transformation wraps a word, `transpose(jvp(x))`, and is peeled off it)
WRAPPERS = frozenset(("checkpoint", "rematted_computation", "while", "body",
                      "cond", "closed_call"))
PASSES = ("forward", "recomputed", "backward")

_WRAPPED = re.compile(r"^([A-Za-z_]\w*)\((.*)\)$")
_WORD = re.compile(r"^[A-Za-z_]\w*$")

Row = Tuple[Tuple[str, ...], str]


def parse(tf_op: str) -> Tuple[Tuple[str, ...], str, str]:
    """(chain, pass, module path) of one `op_name`."""
    head = span_reduce.scope_of(tf_op)
    if head is None:
        return (), "", ""
    if head != "fwd_bwd":
        return (head,), "", ""
    chain, which, modules = [head], "forward", []
    parts = tf_op.rstrip(":").split("/")
    for i, part in enumerate(parts):
        wrappers = []
        while True:
            m = _WRAPPED.match(part)
            if not m:
                break
            wrappers.append(m.group(1))
            part = m.group(2)
        if "transpose" in wrappers and which == "forward":
            which = "backward"
        if part == "rematted_computation":
            which = "recomputed"
        if part in MODEL_NAMES:
            if part not in chain:
                chain.append(part)
        elif (i < len(parts) - 1 and _WORD.match(part)
              and part not in WRAPPERS and part not in NAMES
              and not part.startswith("branch_")
              and "jit" not in wrappers and "pjit" not in wrappers
              and (not modules or modules[-1] != part)):
            modules.append(part)
    return tuple(chain), which, "/".join(modules)


def reduce_device(paths: Sequence[str], steps: int) -> Optional[dict]:
    """The rows and module paths of the device planes in `paths`
    (`.xplane.pb` files of one profiled block of `steps` steps)."""
    t0 = time.perf_counter()
    planes = [p for path in paths for p in span_reduce.read_xspace(path)
              if p["name"].startswith(DEVICE_PLANE_PREFIX)
              and p["lines"].get(OPS_LINE)]
    if not planes:
        return None
    per = 1e12 * len(planes) * steps
    rows: Dict[Row, float] = {}
    modules: Dict[str, float] = {}
    unnamed: Dict[str, float] = {}
    pathless: Dict[str, float] = {}     # kernels under no scope
    parsed: Dict[str, tuple] = {}
    for p in planes:
        for (name, _, _, tf_op), ps in span_reduce.self_times(
                p["lines"][OPS_LINE]):
            if tf_op not in parsed:
                parsed[tf_op] = parse(tf_op)
            chain, which, module = parsed[tf_op]
            rows[chain, which] = rows.get((chain, which), 0.0) + ps / per
            if module:
                modules[module] = modules.get(module, 0.0) + ps / per
            if chain == ("fwd_bwd",):
                tail = "/".join(tf_op.rstrip(":").split("/")[-3:])
                unnamed[tail] = unnamed.get(tail, 0.0) + ps / per
            if not chain and is_kernel(name):
                k = model_scopes.kernel_name(name)
                pathless[k] = pathless.get(k, 0.0) + ps / per
    return {"rows": rows, "modules": modules, "unnamed": unnamed,
            "pathless_kernels": pathless,
            "chips": len(planes), "decode_s": time.perf_counter() - t0}


def total(rows: Dict[Row, float], under: Sequence[str] = (),
          innermost: Optional[str] = None, which: Optional[str] = None,
          without: Sequence[str] = (), exact: bool = False) -> float:
    """Seconds of the rows whose chain holds every name of `under` (is
    `under` itself with `exact`), ends in `innermost`, holds none of
    `without` and whose pass is `which`, as far as each is given."""
    return sum(
        s for (chain, p), s in rows.items()
        if all(n in chain for n in under)
        and (not exact or chain == tuple(under))
        and (innermost is None or chain[-1:] == (innermost,))
        and (which is None or p == which)
        and not any(n in chain for n in without))


def tree_line(rows: Dict[Row, float]) -> str:
    """Every chain with its own and its children's milliseconds, heaviest
    first, a parent's remainder as `rest`: `fwd_bwd 325.2 [moe_experts
    95.4 [moe_to_tokens 40.1, ..., rest 2.0], ..., rest 12.3]`."""
    chains: Dict[Tuple[str, ...], float] = {}
    for (chain, _), s in rows.items():
        for depth in range(len(chain) + 1):
            chains[chain[:depth]] = chains.get(chain[:depth], 0.0) + s

    def show(chain) -> str:
        kids = sorted((c for c in chains
                       if len(c) == len(chain) + 1 and c[:-1] == chain),
                      key=lambda c: -chains[c])
        label = f"{chain[-1]} {1e3 * chains[chain]:.3f}" if chain else ""
        if not kids:
            return label
        rest = total(rows, chain, exact=True)
        inner = ", ".join([show(k) for k in kids]
                          + [f"{'rest' if chain else 'no scope'} "
                             f"{1e3 * rest:.3f}"])
        return f"{label} [{inner}]" if chain else inner

    return show(())


def reduced(run: dict) -> Optional[dict]:
    """The profiled sparse block's reading, made once a run and kept in
    `run`; its two lines for people are printed as it is made."""
    if "scope_tree" in run:
        return run["scope_tree"]
    run["scope_tree"] = out = None
    dirs = (run.get("trace_dirs") or {}).get("sparse")
    traced = [b for b in run["blocks"]["sparse"] if b.get("traced")]
    if not dirs or not traced or not run.get("trace"):
        return None
    run["scope_tree"] = out = reduce_device(find_xplanes(dirs[0]),
                                            traced[0]["steps"])
    if out is None:
        return None
    say, rows = span_reduce.harness.say, out["rows"]
    say(f"scope tree sparse, ms per step on {out['chips']} chip(s), a "
        f"parent's own operations as `rest` (decoded in "
        f"{out['decode_s']:.2f} s): {tree_line(rows)}; under fwd_bwd by "
        f"pass: " + ", ".join(
            f"{p} {1e3 * total(rows, ('fwd_bwd',), which=p):.3f}"
            for p in PASSES)
        + "; kernels whose op_name is no path (in `no scope`, in no pass; "
        "model_scopes gives them to the scope the configuration names): "
        + (_heaviest(out["pathless_kernels"], 6) or "none"))
    blocks: Dict[str, float] = {}   # a model's blocks: two words deep
    for path, s in out["modules"].items():
        key = "/".join(path.split("/")[:2])
        blocks[key] = blocks.get(key, 0.0) + s
    say(f"module paths sparse, ms per step under fwd_bwd: by their first "
        f"two words {_heaviest(blocks, 24)}; the twelve heaviest of "
        f"{len(out['modules'])} whole paths: "
        f"{_heaviest(out['modules'], 12)}; under fwd_bwd and no name of a "
        f"model's, by the end of the path: "
        f"{_heaviest(out['unnamed'], 6) or 'nothing'}")
    return out


def _heaviest(seconds: Dict[str, float], n: int) -> str:
    top = sorted(seconds.items(), key=lambda kv: -kv[1])[:n]
    return ", ".join(f"{k} {1e3 * v:.3f}" for k, v in top)


def _ms(run: dict, name: str, **how) -> Optional[float]:
    r = reduced(run)
    if not r or not any(name in chain for chain, _ in r["rows"]):
        return None
    return 1e3 * total(r["rows"], **how)


def ms(run: dict, name: str, without: Sequence[str] = ()
       ) -> Optional[float]:
    """Milliseconds per step of the operations whose INNERMOST known name
    is `name` (and whose chain holds none of `without`)."""
    return _ms(run, name, innermost=name, without=without)


def under_ms(run: dict, name: str) -> Optional[float]:
    """The same with `name` anywhere on the chain: the scope whole."""
    return _ms(run, name, under=(name,))


def pass_ms(run: dict, which: str) -> Optional[float]:
    """Under `fwd_bwd`, the operations of one of `PASSES`."""
    return _ms(run, "fwd_bwd", under=("fwd_bwd",), which=which)


def unnamed_ms(run: dict) -> Optional[float]:
    """Under `fwd_bwd` and no name of a model's."""
    return _ms(run, "fwd_bwd", under=("fwd_bwd",), exact=True)
