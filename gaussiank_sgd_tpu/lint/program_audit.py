"""gklint v2 tier 2: jaxpr-level program contracts for the jitted step.

The AST tier (``lint/rules``) reasons about source; this tier reasons about
the PROGRAM the source actually builds. It abstract-traces the jitted
train step on the CPU backend for a matrix of build configs — selector ×
wire × overlap × fused — **without executing a single step** (tracing and
lowering only), and checks the compiled-program contracts every README
claim rests on:

* **no host callbacks** — no ``pure_callback`` / ``io_callback`` /
  ``debug_callback`` / infeed / outfeed primitive anywhere in the jaxpr
  (a ``.item()`` or host print smuggled into the step body either shows
  up here or fails the trace outright; both gate);
* **donation is effective** — the lowered StableHLO must mark at least
  ``params + opt_state + EF`` input buffers as donated
  (``jax.buffer_donor`` / ``tf.aliasing_output``), so peak memory claims
  survive refactors;
* **collective inventory** — per-primitive counts (psum / all_gather /
  ppermute) with axis names and scan-body attribution. Pipelined builds
  must issue ≥ 1 payload collective INSIDE the ``lax.scan`` body (that is
  what "overlap" means — the epilogue flush and gtopk tail rounds are
  legitimately outside); sequential builds must issue none inside a scan.
  Axis names must stay inside the build mesh's vocabulary;
* **program fingerprints** — a canonical hash of the traced jaxpr per
  arm, committed to ``.gklint-programs.json``. "Bit-identical" claims
  (wire=auto on an ineligible plan ≡ wire=off; overlap=auto on a
  single-bucket plan ≡ overlap=off) become equality checks, and any PR
  that changes a default-config program must re-baseline explicitly
  (``--write-programs``), which shows up in review as a diff of the
  committed file.

Fingerprints are stable across processes for a fixed jax version, but NOT
across jax versions (the jaxpr pretty-printer is not a stable format). The
committed file records the generating ``jax.__version__``; when the
running version differs, fingerprint comparison downgrades to a warning
while every structural contract above still gates.

Usage::

    python -m gaussiank_sgd_tpu.lint audit                 # check HEAD
    python -m gaussiank_sgd_tpu.lint audit --list-arms
    python -m gaussiank_sgd_tpu.lint audit --arms a,b      # subset
    python -m gaussiank_sgd_tpu.lint audit -o audit.json   # CI artifact
    python -m gaussiank_sgd_tpu.lint audit --write-programs  # re-baseline

Exit codes: 0 all contracts hold, 1 violation/drift, 2 usage error.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import subprocess
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

PROGRAMS_VERSION = 1

#: payload collectives the pipelined scan must own (matches the AST rule)
PAYLOAD_COLLECTIVES = ("all_gather", "ppermute")

#: primitive-name fragments that mean "host round-trip inside the program"
#: (``jax.debug.print`` is its own primitive, ``debug_print``, since jax 0.5)
CALLBACK_MARKERS = ("callback", "debug_print", "infeed", "outfeed")

_HEX_RE = re.compile(r"0x[0-9a-fA-F]+")


def default_programs_path() -> str:
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    return os.path.join(repo, ".gklint-programs.json")


# ---------------------------------------------------------------------------
# the config matrix
# ---------------------------------------------------------------------------
# Every arm is a tiny two-layer MLP (no data, zeros init — only the traced
# program matters) on a 2-device dp mesh. `expect` pins what the build
# must report; `identity` groups arms whose SPARSE program must hash equal.

ARMS: Dict[str, Dict[str, Any]] = {
    "allgather_seq_legacy": dict(
        selector="topk", exchange="allgather", wire="off", overlap="off",
        expect=dict(wire_format="i32f32", overlap="off")),
    "allgather_seq_wire": dict(
        selector="topk", exchange="allgather", wire="auto", overlap="off",
        expect=dict(wire_format="u16bf16", overlap="off")),
    "allgather_pipe_legacy": dict(
        selector="topk", exchange="allgather", wire="off", overlap="auto",
        expect=dict(wire_format="i32f32", overlap="pipelined")),
    "allgather_pipe_wire": dict(
        selector="topk", exchange="allgather", wire="auto", overlap="auto",
        expect=dict(wire_format="u16bf16", overlap="pipelined")),
    "gtopk_seq_legacy": dict(
        selector="topk", exchange="gtopk", wire="off", overlap="off",
        expect=dict(wire_format="i32f32", overlap="off")),
    "gtopk_pipe_wire": dict(
        selector="topk", exchange="gtopk", wire="auto", overlap="auto",
        expect=dict(wire_format="u16bf16", overlap="pipelined")),
    "randomk_pipe_wire": dict(
        selector="randomk", exchange="allgather", wire="auto",
        overlap="auto",
        expect=dict(wire_format="u16bf16", overlap="pipelined")),
    "gaussian_fused_pipe_wire": dict(
        selector="gaussian_fused", exchange="allgather", wire="auto",
        overlap="auto", din=64, width=256, bucket_size=128, density=0.0625,
        expect=dict(wire_format="u16bf16", overlap="pipelined")),
    # wire=auto on a boundary-respecting (non-uniform) plan is INELIGIBLE
    # and must build the bit-identical legacy program
    "greedy_wire_auto_ineligible": dict(
        selector="topk", exchange="allgather", wire="auto", overlap="off",
        policy="greedy",
        expect=dict(wire_format="i32f32", overlap="off"),
        identity="wire-ineligible-equals-legacy"),
    "greedy_wire_off_legacy": dict(
        selector="topk", exchange="allgather", wire="off", overlap="off",
        policy="greedy",
        expect=dict(wire_format="i32f32", overlap="off"),
        identity="wire-ineligible-equals-legacy"),
    # overlap=auto on a single-bucket plan is INELIGIBLE (nothing to
    # pipeline against) and must build the bit-identical sequential program
    "singlebucket_overlap_auto_ineligible": dict(
        selector="topk", exchange="allgather", wire="off", overlap="auto",
        bucket_size=4096,
        expect=dict(wire_format="i32f32", overlap="off"),
        identity="overlap-ineligible-equals-off"),
    "singlebucket_overlap_off": dict(
        selector="topk", exchange="allgather", wire="off", overlap="off",
        bucket_size=4096,
        expect=dict(wire_format="i32f32", overlap="off"),
        identity="overlap-ineligible-equals-off"),
    # the dense twin every parity claim compares against: psum-only,
    # no payload collectives at all
    "dense_reference": dict(
        selector="topk", exchange="allgather", wire="off", overlap="off",
        dense=True,
        expect=dict(wire_format="i32f32", overlap="off")),
}


# ---------------------------------------------------------------------------
# jaxpr walking (no jax import needed: duck-typed on .eqns/.jaxpr)
# ---------------------------------------------------------------------------

def _sub_jaxprs(eqn) -> List[Any]:
    subs: List[Any] = []
    for v in eqn.params.values():
        if hasattr(v, "jaxpr"):
            subs.append(v.jaxpr)
        elif hasattr(v, "eqns"):
            subs.append(v)
        elif isinstance(v, (list, tuple)):
            for x in v:
                if hasattr(x, "jaxpr"):
                    subs.append(x.jaxpr)
                elif hasattr(x, "eqns"):
                    subs.append(x)
    return subs


def collect_primitives(jaxpr, in_scan: bool = False,
                       out: Optional[List[Tuple[str, bool, Any]]] = None
                       ) -> List[Tuple[str, bool, Any]]:
    """Flat list of ``(prim_name, inside_scan_body, params)`` over the
    whole nested jaxpr."""
    if out is None:
        out = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        out.append((name, in_scan, eqn.params))
        child_in_scan = in_scan or name == "scan"
        for sub in _sub_jaxprs(eqn):
            collect_primitives(sub, child_in_scan, out)
    return out


def find_callbacks(prims: Sequence[Tuple[str, bool, Any]]) -> List[str]:
    return sorted({name for name, _, _ in prims
                   if any(m in name for m in CALLBACK_MARKERS)})


def collective_inventory(prims: Sequence[Tuple[str, bool, Any]]
                         ) -> Dict[str, Dict[str, Any]]:
    inv: Dict[str, Dict[str, Any]] = {}
    for name, in_scan, params in prims:
        if name not in PAYLOAD_COLLECTIVES and not name.startswith("psum"):
            continue
        ent = inv.setdefault(name, {"total": 0, "in_scan": 0,
                                    "axes": set()})
        ent["total"] += 1
        ent["in_scan"] += int(in_scan)
        axes = params.get("axis_name", params.get("axes", ()))
        if isinstance(axes, str):
            axes = (axes,)
        for ax in axes or ():
            if isinstance(ax, str):
                ent["axes"].add(ax)
    for ent in inv.values():
        ent["axes"] = sorted(ent["axes"])
    return inv


def canonical_fingerprint(jaxpr_text: str) -> str:
    """sha256 of the jaxpr pretty-print with memory addresses scrubbed."""
    return hashlib.sha256(
        _HEX_RE.sub("0xX", jaxpr_text).encode()).hexdigest()[:16]


def check_contracts(arm: str, spec: Dict[str, Any], built: Dict[str, Any]
                    ) -> List[str]:
    """Violation strings for one traced arm (empty == contract holds)."""
    bad: List[str] = []
    expect = spec.get("expect", {})
    for key, want in expect.items():
        got = built.get(key)
        if got != want:
            bad.append(f"{arm}: build reported {key}={got!r}, "
                       f"expected {want!r}")
    if built["callbacks"]:
        bad.append(f"{arm}: host callback primitive(s) in the step "
                   f"program: {', '.join(built['callbacks'])}")
    inv = built["collectives"]
    payload_in_scan = sum(inv.get(p, {}).get("in_scan", 0)
                          for p in PAYLOAD_COLLECTIVES)
    payload_total = sum(inv.get(p, {}).get("total", 0)
                        for p in PAYLOAD_COLLECTIVES)
    if spec.get("dense"):
        if payload_total:
            bad.append(f"{arm}: dense program must not issue payload "
                       f"collectives, found {payload_total}")
    elif expect.get("overlap") == "pipelined":
        if payload_in_scan < 1:
            bad.append(f"{arm}: pipelined build has no payload collective "
                       f"inside the scan body — the exchange is not "
                       f"overlapped with compression")
    else:
        if payload_in_scan:
            bad.append(f"{arm}: sequential build issues {payload_in_scan} "
                       f"payload collective(s) inside a scan body")
    mesh_axes: Set[str] = set(built["mesh_axes"])
    for name, ent in inv.items():
        stray = set(ent["axes"]) - mesh_axes
        if stray:
            bad.append(f"{arm}: {name} uses axis names {sorted(stray)} "
                       f"outside the mesh vocabulary {sorted(mesh_axes)}")
    if built["donated"] < built["donatable"]:
        bad.append(f"{arm}: only {built['donated']} of "
                   f"{built['donatable']} params/opt/EF input buffers are "
                   f"donated in the lowered program — donation regressed")
    return bad


# ---------------------------------------------------------------------------
# tracing one arm (the only part that imports jax)
# ---------------------------------------------------------------------------

def _ensure_cpu_devices(n: int) -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from .. import virtual_cpu
    try:
        virtual_cpu.provision(n)
    except RuntimeError:
        pass  # backend already initialized (e.g. under the test session)
    import jax
    if len(jax.devices()) < n:
        raise RuntimeError(
            f"program audit needs >= {n} CPU devices, found "
            f"{len(jax.devices())}; run in a fresh process or provision "
            f"a wider virtual platform first")


def trace_arm(name: str, spec: Dict[str, Any], mesh) -> Dict[str, Any]:
    """Build one config arm and return its audited program facts.

    Traces (``jax.make_jaxpr``) and lowers (``.lower().as_text()``) the
    step; never compiles or executes it.
    """
    import jax
    import jax.numpy as jnp
    import optax

    from ..compressors import get_compressor
    from ..parallel.bucketing import plan_for_params
    from ..parallel.mesh import shard_batch
    from ..parallel.trainstep import build_dp_train_step

    din = spec.get("din", 16)
    width = spec.get("width", 32)
    dout = 4
    density = spec.get("density", 0.25)
    bucket_size = spec.get("bucket_size", 64)
    policy = spec.get("policy", "uniform")

    params = {"w1": jnp.zeros((din, width), jnp.float32),
              "b1": jnp.zeros((width,), jnp.float32),
              "w2": jnp.zeros((width, dout), jnp.float32),
              "b2": jnp.zeros((dout,), jnp.float32)}

    def loss_fn(p, mstate, batch, rng):
        x, y = batch
        h = jnp.tanh(x @ p["w1"] + p["b1"])
        out = h @ p["w2"] + p["b2"]
        mse = jnp.mean((out - y) ** 2)
        return mse, (mstate, {"mse": mse})

    comp = get_compressor(spec["selector"], density=density)
    plan = plan_for_params(params, density=density, bucket_size=bucket_size,
                           policy=policy)
    ts = build_dp_train_step(
        loss_fn, optax.sgd(0.1), comp, plan, mesh,
        num_microbatches=1, clip_norm=0.0,
        exchange=spec.get("exchange", "allgather"),
        wire=spec.get("wire", "auto"),
        overlap=spec.get("overlap", "auto"))
    state = ts.init_state(params, jax.random.PRNGKey(0))
    batch = shard_batch(mesh, (jnp.zeros((8, din), jnp.float32),
                               jnp.zeros((8, dout), jnp.float32)))

    step_fn = ts.dense_step if spec.get("dense") else ts.sparse_step
    closed = jax.make_jaxpr(step_fn)(state, batch)
    prims = collect_primitives(closed.jaxpr)
    lowered_text = step_fn.lower(state, batch).as_text()
    donated = (lowered_text.count("jax.buffer_donor")
               + lowered_text.count("tf.aliasing_output"))
    leaves = jax.tree_util.tree_leaves
    donatable = (len(leaves(state.params)) + len(leaves(state.opt_state))
                 + 1)  # + the flat EF residual buffer
    return {
        "config": {k: v for k, v in spec.items()
                   if k not in ("expect", "identity")},
        "wire_format": ts.wire_format,
        "overlap": "off" if spec.get("dense") else ts.overlap,
        "ef_numel": int(ts.ef_numel),
        "mesh_axes": [str(a) for a in mesh.axis_names],
        "fingerprint": canonical_fingerprint(str(closed)),
        "collectives": collective_inventory(prims),
        "callbacks": find_callbacks(prims),
        "donated": donated,
        "donatable": donatable,
        "n_primitives": len(prims),
    }


# ---------------------------------------------------------------------------
# the audit driver
# ---------------------------------------------------------------------------

def _git_rev() -> Optional[str]:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(default_programs_path()),
            capture_output=True, text=True, check=True, timeout=10)
        return out.stdout.strip() or None
    except (OSError, subprocess.SubprocessError):
        return None


def run_audit(arm_names: Optional[Sequence[str]] = None,
              mesh_devices: int = 2) -> Dict[str, Any]:
    """Trace + audit every requested arm; returns the full report dict
    (no baseline comparison here — see :func:`compare_programs`)."""
    _ensure_cpu_devices(mesh_devices)
    import jax

    from ..parallel.mesh import data_parallel_mesh
    mesh = data_parallel_mesh(devices=jax.devices()[:mesh_devices])

    names = list(arm_names) if arm_names else list(ARMS)
    unknown = [n for n in names if n not in ARMS]
    if unknown:
        raise KeyError(f"unknown arm(s): {', '.join(unknown)} "
                       f"(available: {', '.join(ARMS)})")

    arms: Dict[str, Any] = {}
    violations: List[str] = []
    for name in names:
        spec = ARMS[name]
        try:
            built = trace_arm(name, spec, mesh)
        except Exception as e:  # a build/trace failure IS a finding
            violations.append(
                f"{name}: build/trace failed: {type(e).__name__}: {e}")
            arms[name] = {"error": f"{type(e).__name__}: {e}"}
            continue
        arms[name] = built
        violations.extend(check_contracts(name, spec, built))

    identities: List[Dict[str, Any]] = []
    groups: Dict[str, List[str]] = {}
    for name in names:
        g = ARMS[name].get("identity")
        if g:
            groups.setdefault(g, []).append(name)
    for g, members in groups.items():
        if len(members) < 2:
            continue  # subset run: nothing to compare
        fps = {m: arms[m].get("fingerprint") for m in members}
        equal = len(set(fps.values())) == 1 and None not in fps.values()
        identities.append({"group": g, "arms": members, "equal": equal})
        if not equal:
            violations.append(
                f"identity '{g}' broken: programs differ across "
                f"{members} ({fps}) — an 'off/ineligible' path is no "
                f"longer bit-identical to its reference build")

    return {
        "version": PROGRAMS_VERSION,
        "tool": "gklint-audit",
        "jax_version": jax.__version__,
        "git_rev": _git_rev(),
        "mesh_devices": mesh_devices,
        "platform": "cpu",
        "arms": arms,
        "identities": identities,
        "violations": violations,
        "ok": not violations,
    }


# ---------------------------------------------------------------------------
# the committed-fingerprint ratchet
# ---------------------------------------------------------------------------

def programs_snapshot(report: Dict[str, Any]) -> Dict[str, Any]:
    """The subset of a report committed to ``.gklint-programs.json``."""
    return {
        "version": PROGRAMS_VERSION,
        "jax_version": report["jax_version"],
        "mesh_devices": report["mesh_devices"],
        "git_rev": report.get("git_rev"),
        "fingerprints": {
            name: arm["fingerprint"]
            for name, arm in sorted(report["arms"].items())
            if "fingerprint" in arm},
    }


def load_programs(path: str) -> Optional[Dict[str, Any]]:
    try:
        with open(path, "r", encoding="utf-8") as fh:
            data = json.load(fh)
    except (OSError, ValueError):
        return None
    if not isinstance(data, dict) or "fingerprints" not in data:
        return None
    return data


def compare_programs(report: Dict[str, Any], baseline: Dict[str, Any],
                     partial: bool = False
                     ) -> Tuple[List[str], List[str]]:
    """(violations, warnings) from checking a report against the committed
    snapshot. ``partial`` (an ``--arms`` subset run) skips missing-arm
    checks."""
    violations: List[str] = []
    warnings: List[str] = []
    if baseline.get("jax_version") != report["jax_version"]:
        warnings.append(
            f"committed fingerprints were generated under jax "
            f"{baseline.get('jax_version')}, running {report['jax_version']}"
            f" — jaxpr text is not stable across jax versions, so "
            f"fingerprint drift is NOT gating this run (structural "
            f"contracts still are); re-baseline on the pinned version")
        return violations, warnings
    current = programs_snapshot(report)["fingerprints"]
    committed = baseline["fingerprints"]
    for name, fp in sorted(current.items()):
        if name not in committed:
            violations.append(
                f"{name}: no committed fingerprint — a new config arm "
                f"must be baselined explicitly (--write-programs)")
        elif committed[name] != fp:
            violations.append(
                f"{name}: program fingerprint drifted "
                f"({committed[name]} -> {fp}) — the compiled step program "
                f"changed; if intended, re-baseline with --write-programs "
                f"so the change is an explicit reviewed diff")
    if not partial:
        for name in sorted(set(committed) - set(current)):
            violations.append(
                f"{name}: committed fingerprint has no current arm — "
                f"removed arms must be re-baselined (--write-programs)")
    return violations, warnings
