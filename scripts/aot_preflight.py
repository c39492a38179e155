#!/usr/bin/env python3
"""Device-less AOT pre-flight: compile the step programs for a v5e without one.

libtpu compiles for a *topology description* on a machine that has no chip, so
the full dense and sparse step — Mosaic kernel included — can be compiled here
on the CPU host before chip budget is spent on a run that would have died in
the compiler. It prints, per program: compile seconds, ``tpu_custom_call``
count, the collectives left in the optimized HLO, XLA's FLOP count, its
memory analysis, and what the program does AFTER the gradient as a count of
work: the bytes that the optimized HLO's operations read and write under each
of the step's own scopes, in passes over one n-vector (``passes_by_scope``;
ROADMAP S10's list is sized from this), and for a model that names its parts
(the language models built from ``models/blocks/``) which of its scopes the
compiled program carries on forward, recomputed and backward instructions
(``instructions_by_scope_and_pass``). The optimizer is the cells' (momentum
0.9 and a weight decay), so the program compiled is the one a cell runs.

It proves COMPILATION ONLY. It runs nothing: not start-up, not placement, not
numerics, not time. Those are chip_smoke.py's, on the chip.

    python scripts/aot_preflight.py                       # vgg16, one chip
    python scripts/aot_preflight.py --chips 4
    python scripts/aot_preflight.py --model transformer --dataset wmt \\
        --batch-size 32
    python scripts/aot_preflight.py --model mellum2 --dataset ptb \\
        --batch-size 2 --model-kwargs '{"num_layers": 4, "expert_shares": 8,
        "vocab_size": 12288, "seq_len": 8192, "kernels": true}'
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

# the metadata-server query has nothing to answer it on a sealed machine;
# and no TPU client is created here, so libtpu's one-process lockfile (held
# by whatever else has libtpu loaded) guards nothing
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import topologies
from jax.sharding import Mesh

from benchmarks import scope_tree
from benchmarks.span_reduce import scope_of
from gaussiank_sgd_tpu.compressors import get_compressor
from gaussiank_sgd_tpu.models import get_model
from gaussiank_sgd_tpu.parallel.bucketing import plan_for_params
from gaussiank_sgd_tpu.parallel.flat_opt import FlatSGDM
from gaussiank_sgd_tpu.parallel.trainstep import build_dp_train_step
from gaussiank_sgd_tpu.training.losses import make_loss_fn

_COLLECTIVE = re.compile(
    r" (all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")


# ------------------------------------------------ passes over an n-vector
# What the step program does after the gradient is elementwise work over
# vectors of n parameters, so its cost is how often it streams one. This
# counts that from the optimized HLO: a count of work, never a time.

# the scopes printed: what follows the gradient (benchmarks/span_reduce.py
# names them all and reads them from an operation's `op_name`)
_PRINTED = ("flatten", "ef_select", "scatter", "update", "guard",
            "step_metrics")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8}
_ARRAY = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")
_INSTR = re.compile(r"^\s*(ROOT )?%([\w.\-]+) = ")
_NAME = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"(?:calls|body|(?:true|false)_computation)=%([\w.\-]+)"
                     r"|branch_computations=\{([^}]*)\}")
# operations that move nothing: views, tuples, and the half of an async
# pair that only waits
_FREE = frozenset((
    "parameter", "tuple", "get-tuple-element", "bitcast", "constant", "iota",
    "after-all", "partition-id", "replica-id", "opt-barrier", "copy-done",
    "slice-done", "async-done", "all-reduce-done", "all-gather-done",
    "collective-permute-done"))
_VIEWS = ("bitcast", "reshape", "copy", "convert")


def _type_bytes(t: str) -> int:
    """Bytes of an HLO type; a tuple's are its elements' sum."""
    total = 0
    for dtype, dims in _ARRAY.findall(t):
        size = _DTYPE_BYTES.get(dtype, 0)    # token[], opaque: nothing
        for d in dims.split(","):
            size *= int(d) if d else 1
        total += size
    return total


def _balanced(s: str, i: int) -> int:
    """Index just past the parenthesis that closes the one at ``s[i]``."""
    depth = 0
    for j in range(i, len(s)):
        depth += (s[j] == "(") - (s[j] == ")")
        if depth == 0:
            return j + 1
    return len(s)


def _parse_hlo(hlo: str):
    """({computation: [instruction]}, entry's name) of an HLO module's text;
    an instruction is a dict of name, type, op, args (the text between its
    parentheses), operands (the names in it), attrs (what follows), root."""
    comps, cur, entry = {}, None, None
    for line in hlo.splitlines():
        if line.endswith("{") and "->" in line and line[:1] not in " \t":
            head = line.split()
            is_entry = head[0] == "ENTRY"
            name = head[1 if is_entry else 0].lstrip("%")
            cur = comps.setdefault(name, [])
            entry = name if is_entry else entry
            continue
        m = _INSTR.match(line)
        if m is None or cur is None:
            continue
        rest = line[m.end():]
        cut = _balanced(rest, 0) if rest.startswith("(") else rest.index(" ")
        typ, rest = rest[:cut], rest[cut:].lstrip()
        par = rest.index("(")
        end = _balanced(rest, par)
        args = rest[par + 1:end - 1]
        cur.append({"name": m.group(2), "type": typ, "op": rest[:par],
                    "args": args, "operands": _NAME.findall(args),
                    "attrs": rest[end:], "root": bool(m.group(1))})
    return comps, entry


def _called(instr) -> list:
    out = []
    for one, many in _CALLED.findall(instr["attrs"]):
        out += [one] if one else _NAME.findall(many)
    return out


def _fusion_bytes(instr, types, comps):
    """(read, written) of one fusion. An operand that the fused computation
    only slices is read as far as its slices go. A fusion that ends in a
    ``dynamic-update-slice`` or a ``scatter`` into one of its operands
    writes the update, in place, and does not read the buffer that it
    updates (XLA aliases that operand with the result)."""
    body = comps.get((_called(instr) or [""])[0], [])
    by_name = {i["name"]: i for i in body}
    users = {}
    for i in body:
        for o in i["operands"]:
            users.setdefault(o, []).append(i)

    def behind_views(i):
        while i is not None and i["op"] in _VIEWS and i["operands"]:
            i = by_name.get(i["operands"][0])
        return i

    written = _type_bytes(instr["type"])
    in_place = set()
    root = next((i for i in body if i["root"]), None)
    roots = ([by_name.get(o) for o in root["operands"]]
             if root is not None and root["op"] == "tuple" else [root])
    for r in map(behind_views, roots):
        if r is None or r["op"] not in ("dynamic-update-slice", "scatter"):
            continue
        target = behind_views(by_name.get(r["operands"][0]))
        update = by_name.get(
            r["operands"][1 if r["op"] == "dynamic-update-slice" else -1])
        if target is not None and target["op"] == "parameter" \
                and update is not None:
            in_place.add(target["name"])
            written += _type_bytes(update["type"]) - _type_bytes(r["type"])
    read = 0
    for i in body:
        if i["op"] != "parameter" or i["name"] in in_place:
            continue
        uses = users.get(i["name"], [])
        full = _type_bytes(types.get(instr["operands"][int(i["args"])],
                                     i["type"]))
        if uses and all(u["op"] in ("slice", "dynamic-slice") for u in uses):
            read += min(full, sum(_type_bytes(u["type"]) for u in uses))
        else:
            read += full
    return read, written


def passes_by_scope(hlo: str, n: int) -> dict:
    """{label: [passes read, passes written]} of a compiled step program:
    the bytes that the optimized HLO's operations read and write, by the
    program's scope on their ``op_name``, in units of one float32 n-vector
    (4 n bytes). A ``while``'s body counts once; of a ``conditional``'s
    branches the one that moves the most counts (the step that commits,
    not the step that the guard skips). Under no scope only the operations
    that are nothing but movement count, as ``"no scope, <opcode>"``: a
    concatenation's ``dynamic-update-slice`` and the copies lose their
    ``op_name``."""
    comps, entry = _parse_hlo(hlo)
    unit = 4.0 * n

    def walk(comp) -> dict:
        acc = {}

        def add(label, read, written):
            a = acc.setdefault(label, [0.0, 0.0])
            a[0] += read
            a[1] += written

        types = {i["name"]: i["type"] for i in comps[comp]}
        for i in comps[comp]:
            op = i["op"]
            if op in _FREE:
                continue
            if op in ("conditional", "while", "call"):
                inner = [walk(c) for c in _called(i)]
                if op == "conditional":
                    inner = [max(inner, key=lambda d: sum(
                        map(sum, d.values())))]
                for d in inner:
                    for label, (read, written) in d.items():
                        add(label, read, written)
                continue
            if op == "fusion":
                read, written = _fusion_bytes(i, types, comps)
            elif op == "dynamic-update-slice":
                read = written = _type_bytes(types.get(i["operands"][1], ""))
            elif op in ("slice", "dynamic-slice", "copy", "copy-start",
                        "slice-start"):
                read = written = _type_bytes(i["type"]) // (
                    2 if op.endswith("-start") else 1)
            else:
                read = sum(_type_bytes(types.get(o, ""))
                           for o in i["operands"])
                written = _type_bytes(i["type"])
            m = _OP_NAME.search(i["attrs"])
            scope = scope_of(m.group(1)) if m else None
            if scope in _PRINTED:
                add(scope, read / unit, written / unit)
            elif scope is None and op in ("dynamic-update-slice", "copy"):
                add(f"no scope, {op}", read / unit, written / unit)
        return acc

    acc = walk(entry)
    return {**{s: [0.0, 0.0] for s in _PRINTED}, **acc}


def print_passes(hlo: str, n: int) -> None:
    print(f"    passes over one n-vector ({4 * n / 1e9:.3f} GB) by scope, "
          f"read + written (a count of work from the optimized HLO):")
    for label, (r, w) in passes_by_scope(hlo, n).items():
        print(f"      {label:<31}{r:6.2f} + {w:5.2f} = {r + w:6.2f}")


def instructions_by_scope_and_pass(hlo: str) -> dict:
    """{a model's scope: [forward, recomputed, backward]}: the optimized
    HLO's instructions whose ``op_name`` ends in that scope (its innermost
    known name, ``benchmarks/scope_tree.parse``), by the pass the path
    says; ``""`` stands for ``fwd_bwd`` and no name of a model's. A count of
    instructions: what a trace of this program CAN name, never a time."""
    out: dict = {}
    for name in _OP_NAME.findall(hlo):
        chain, which, _ = scope_tree.parse(name)
        if chain[:1] == ("fwd_bwd",):
            row = out.setdefault(chain[-1] if len(chain) > 1 else "",
                                 [0, 0, 0])
            row[scope_tree.PASSES.index(which)] += 1
    return out


def print_model_scopes(hlo: str) -> None:
    counts = instructions_by_scope_and_pass(hlo)
    if set(counts) <= {""}:
        return
    print("    instructions under fwd_bwd by the model's innermost scope: "
          "forward / recomputed / backward")
    for scope in sorted(counts, key=lambda k: (k == "", k)):
        f, r, b = counts[scope]
        print(f"      {scope or 'no name of the model':<31}{f:6d} /{r:6d} /"
              f"{b:6d}")


def _batch_shapes(spec, batch_size: int):
    """The (x, y) shapes and dtypes of one batch of the model task."""
    def ints(*shape):
        return jax.ShapeDtypeStruct((batch_size,) + shape, jnp.int32)
    x_float = jax.ShapeDtypeStruct((batch_size,) + spec.input_shape,
                                   jnp.float32)
    if spec.task == "classify":
        return x_float, ints()
    if spec.task in ("lm", "seq2seq"):
        return ints(spec.input_shape[0]), ints(spec.input_shape[0])
    if spec.task == "ctc":
        return x_float, ints(16)
    raise ValueError(spec.task)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="aot_preflight.py")
    ap.add_argument("--model", default="vgg16")
    ap.add_argument("--dataset", default="cifar10")
    ap.add_argument("--batch-size", type=int, default=128,
                    help="per worker")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 2, 4))
    ap.add_argument("--compressor", default="auto")
    ap.add_argument("--density", type=float, default=0.001)
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--model-kwargs", type=json.loads, default={},
                    help="JSON, as TrainConfig.model_kwargs")
    args = ap.parse_args(argv)

    topo = topologies.get_topology_desc(args.topology, "tpu")
    mesh = Mesh(np.array(topo.devices[:args.chips]), ("dp",))
    print(f"target: {args.chips} x {topo.devices[0].device_kind!r} "
          f"(device-less); this process's backend: {jax.default_backend()}")

    spec = get_model(args.model, args.dataset, dtype=jnp.bfloat16,
                     **args.model_kwargs)
    recurrent = args.model == "lstm"
    batch = _batch_shapes(spec, args.batch_size * args.chips)
    two = _batch_shapes(spec, 2)
    init_in = two if spec.task == "seq2seq" else two[:1]
    variables = jax.eval_shape(
        lambda *a: spec.module.init({"params": jax.random.PRNGKey(0)}, *a,
                                    train=False), *init_in)
    params = variables["params"]
    mstate = {k: v for k, v in variables.items() if k != "params"}
    plan = plan_for_params(params, args.density)
    ts = build_dp_train_step(
        make_loss_fn(spec, recurrent=recurrent), None,
        get_compressor(args.compressor, density=args.density), plan, mesh,
        recurrent=recurrent,
        flat_opt=FlatSGDM(lr=0.1, momentum=0.9, weight_decay=1e-4))
    carry = (jax.eval_shape(lambda: spec.module.initial_carry(
        args.batch_size * args.chips)) if recurrent else ())
    state = jax.eval_shape(
        lambda p, m, c: ts.init_state(p, jax.random.PRNGKey(2),
                                      model_state=m, carry=c),
        params, mstate, carry)
    print(f"{args.model}: {plan.total_numel} parameters, "
          f"{len(plan.buckets)} bucket(s), k={plan.total_k}, "
          f"ef_numel={ts.ef_numel}, kernel={ts.kernel_mode}, "
          f"wire={ts.wire_format}, overlap={ts.overlap}")

    for name, fn in (("dense", ts.dense_step), ("sparse", ts.sparse_step)):
        lowered = fn.lower(state, batch)
        t0 = time.perf_counter()
        compiled = lowered.compile()
        print(f"{name}: compiled in {time.perf_counter() - t0:.1f}s, "
              f"{lowered.as_text().count('tpu_custom_call')} "
              f"tpu_custom_call, "
              f"{compiled.cost_analysis().get('flops', 0):.4g} flop/step")
        hlo = compiled.as_text()
        for line in hlo.splitlines():
            if _COLLECTIVE.search(line):
                print("   ", line.strip()[:200])
        print("   ", compiled.memory_analysis())
        print_passes(hlo, plan.total_numel)
        print_model_scopes(hlo)


if __name__ == "__main__":
    main()
