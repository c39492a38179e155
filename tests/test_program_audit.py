"""gklint v2 program tier (lint/program_audit.py): the jaxpr-level
contracts the CI ratchet gates on.

The module-scoped ``report`` fixture traces a 4-arm subset once (sequential
+ pipelined + the wire-ineligibility identity pair) on the shared 8-device
test session — the auditor pins its mesh to the first 2 devices, matching
the committed ``.gklint-programs.json`` (generated at ``mesh_devices=2``).
Tracing only: nothing here compiles or executes a step.
"""

import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from gaussiank_sgd_tpu.lint.program_audit import (
    ARMS, PAYLOAD_COLLECTIVES, canonical_fingerprint, check_contracts,
    collect_primitives, compare_programs, default_programs_path,
    find_callbacks, load_programs, programs_snapshot, run_audit,
)

SUBSET = ["allgather_seq_legacy", "allgather_pipe_wire",
          "greedy_wire_auto_ineligible", "greedy_wire_off_legacy"]


@pytest.fixture(scope="module")
def report():
    return run_audit(SUBSET)


def _payload_in_scan(arm):
    return sum(arm["collectives"].get(p, {}).get("in_scan", 0)
               for p in PAYLOAD_COLLECTIVES)


# ------------------------------------------------------- contracts on HEAD

def test_head_arms_trace_clean(report):
    assert report["violations"] == []
    assert set(report["arms"]) == set(SUBSET)
    assert all("error" not in a for a in report["arms"].values())


def test_pipelined_arm_owns_an_in_scan_collective(report):
    # the definition of "overlap": the payload exchange for chunk i is
    # issued inside the scan body while chunk i+1 compresses
    assert _payload_in_scan(report["arms"]["allgather_pipe_wire"]) >= 1
    assert _payload_in_scan(report["arms"]["allgather_seq_legacy"]) == 0


def test_no_host_callbacks_in_any_head_arm(report):
    assert all(a["callbacks"] == [] for a in report["arms"].values())


def test_donation_effective_in_lowered_programs(report):
    for arm in report["arms"].values():
        assert arm["donated"] >= arm["donatable"]


def test_wire_ineligible_identity_holds(report):
    idents = {i["group"]: i for i in report["identities"]}
    ident = idents["wire-ineligible-equals-legacy"]
    assert ident["equal"], ident


# ------------------------------------------------- the committed ratchet

def test_head_matches_committed_fingerprints(report):
    baseline = load_programs(default_programs_path())
    assert baseline is not None, (
        ".gklint-programs.json missing/corrupt — regenerate with "
        "python -m gaussiank_sgd_tpu.lint audit --write-programs")
    violations, warnings = compare_programs(report, baseline, partial=True)
    if baseline["jax_version"] == report["jax_version"]:
        assert violations == [], "\n".join(violations)
    else:
        # cross-version runs downgrade fingerprint drift to a warning
        assert warnings and "NOT gating" in warnings[0]


def test_compare_programs_flags_drift_and_unbaselined_arms(report):
    baseline = json.loads(json.dumps(programs_snapshot(report)))
    name = "allgather_pipe_wire"
    baseline["fingerprints"][name] = "0" * 16
    violations, _ = compare_programs(report, baseline, partial=True)
    assert any(name in v and "drifted" in v for v in violations)

    del baseline["fingerprints"][name]
    baseline["fingerprints"]["allgather_seq_legacy"] = (
        report["arms"]["allgather_seq_legacy"]["fingerprint"])
    violations, _ = compare_programs(report, baseline, partial=True)
    assert any(name in v and "no committed fingerprint" in v
               for v in violations)


def test_cross_jax_version_downgrades_to_warning(report):
    baseline = programs_snapshot(report)
    baseline["jax_version"] = "0.0.0-other"
    violations, warnings = compare_programs(report, baseline)
    assert violations == []
    assert warnings and "jax" in warnings[0]


def test_fingerprint_scrubs_memory_addresses():
    a = canonical_fingerprint("custom_call target=0xdeadbeef scan[]")
    b = canonical_fingerprint("custom_call target=0x1234 scan[]")
    assert a == b
    assert a != canonical_fingerprint("custom_call target=0xdead psum[]")


# -------------------------------------------- deliberate contract breaks

def test_callback_primitive_is_detected():
    def noisy(x):
        jax.debug.print("x={x}", x=x)
        return x * 2

    closed = jax.make_jaxpr(noisy)(jnp.zeros(4))
    prims = collect_primitives(closed.jaxpr)
    assert find_callbacks(prims) == ["debug_print"]

    def quiet(x):
        return jax.pure_callback(lambda v: v, x, x) * 2

    closed = jax.make_jaxpr(quiet)(jnp.zeros(4))
    assert find_callbacks(collect_primitives(closed.jaxpr)) == [
        "pure_callback"]


def test_callback_in_step_program_violates_contract(report):
    built = dict(report["arms"]["allgather_seq_legacy"])
    built["callbacks"] = ["debug_callback"]
    bad = check_contracts("fake_arm", ARMS["allgather_seq_legacy"], built)
    assert any("host callback" in v for v in bad)


def test_sequential_program_fails_pipelined_contract(report):
    # checking the sequential build against the pipelined expectation must
    # name both breaks: the knob mismatch AND the missing in-scan exchange
    built = report["arms"]["allgather_seq_legacy"]
    spec = {"expect": {"overlap": "pipelined"}}
    bad = check_contracts("fake_arm", spec, built)
    assert any("overlap" in v and "expected 'pipelined'" in v for v in bad)
    assert any("inside the scan body" in v for v in bad)


def test_donation_regression_violates_contract(report):
    built = dict(report["arms"]["allgather_seq_legacy"])
    built["donated"] = 0
    bad = check_contracts("fake_arm", ARMS["allgather_seq_legacy"], built)
    assert any("donat" in v for v in bad)


def test_unknown_arm_is_a_usage_error():
    with pytest.raises(KeyError):
        run_audit(["no_such_arm"])


# ------------------------------------------------------------------- CLI

def test_cli_list_arms_is_fast_and_jax_free():
    # --list-arms must not trace (and must run before any device init)
    r = subprocess.run(
        [sys.executable, "-m", "gaussiank_sgd_tpu.lint", "audit",
         "--list-arms"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0
    for name in ARMS:
        assert name in r.stdout
