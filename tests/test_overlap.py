"""Bucket-pipelined step schedule (ISSUE 7).

The contract under test: `--overlap auto` on a pipeline-eligible build
(uniform plan, >= 2 buckets) compiles the two-phase lax.scan schedule and
is BIT-IDENTICAL to the sequential program after N steps — params, opt
state, EF residual, compressor state — across both exchange paths, both
wire modes, rng-consuming selectors, the flat optimizer, and the fused
EF+select kernel. Ineligible builds and `--overlap off` keep the
sequential program. Plus: the
overlapped-bytes metric, elastic restore across overlap geometry, and
the policy-engine treatment of the overlap knob as a program-layout
change (arm-record reset + recompile charge, mirroring density/bucket).

All on the virtual 8-device CPU mesh from conftest.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from gaussiank_sgd_tpu.compressors import get_compressor
from gaussiank_sgd_tpu.parallel.bucketing import plan_for_params
from gaussiank_sgd_tpu.parallel.flat_opt import FlatSGDM
from gaussiank_sgd_tpu.parallel.mesh import data_parallel_mesh, shard_batch
from gaussiank_sgd_tpu.parallel.trainstep import build_dp_train_step
from gaussiank_sgd_tpu.policy import (OverlapPromotionRule, PolicyDecision,
                                      PolicyEngine, PolicySignals)
from gaussiank_sgd_tpu.policy.rules import (KNOB_BUCKET, KNOB_COMPRESSOR,
                                            KNOB_OVERLAP, RuleContext)
from gaussiank_sgd_tpu.policy.signals import SignalSnapshot
from gaussiank_sgd_tpu.training.checkpoint import (restore_checkpoint,
                                                   save_checkpoint)

from test_trainstep import make_problem


def _leaves_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


def _build_pair(compressor="topk", density=0.25, bucket_size=128,
                flat=False, n_steps=3, **kw):
    """(sequential, pipelined) runs of the same problem on one uniform
    plan; returns both final states + last-step metrics + the builds."""
    params, loss_fn, make_batch = make_problem()
    mesh = data_parallel_mesh()
    plan = plan_for_params(params, density, bucket_size, policy="uniform")
    batch = shard_batch(mesh, make_batch(64))
    outs = []
    for overlap in ("off", "auto"):
        spec = get_compressor(compressor, density=density)
        if flat:
            opt, kw2 = None, dict(kw, flat_opt=FlatSGDM(0.05, momentum=0.9))
        else:
            opt, kw2 = optax.sgd(0.05, momentum=0.9), kw
        ts = build_dp_train_step(loss_fn, opt, spec, plan, mesh,
                                 overlap=overlap, **kw2)
        state = ts.init_state(params, jax.random.PRNGKey(42))
        m = None
        for _ in range(n_steps):
            state, m = ts.sparse_step(state, batch)
        outs.append((ts, state, m))
    return outs


def _assert_bit_identical(outs):
    (ts_a, sa, ma), (ts_b, sb, mb) = outs
    assert ts_a.overlap == "off"
    assert ts_b.overlap == "pipelined"
    assert _leaves_equal(sa.params, sb.params)
    assert _leaves_equal(sa.opt_state, sb.opt_state)
    assert np.array_equal(np.asarray(sa.ef_residual),
                          np.asarray(sb.ef_residual))
    assert _leaves_equal(sa.comp_state, sb.comp_state)
    # the overlapped-bytes metric: zero on the sequential program,
    # positive on the pipelined one (payloads launched from the scan)
    assert float(ma.overlapped_bytes_sent) == 0.0
    assert float(mb.overlapped_bytes_sent) > 0.0
    assert float(mb.overlapped_bytes_sent) <= float(mb.bytes_sent)


# ------------------------------------------------------- N-step bit parity

@pytest.mark.parametrize("exchange,wire", [
    ("allgather", "off"), ("allgather", "auto"),
    ("gtopk", "off"), ("gtopk", "auto"),
])
def test_pipelined_bit_identity_exchange_x_wire(exchange, wire):
    """The core acceptance: pipelined == sequential bitwise after N
    steps, on both exchange paths x both wire modes."""
    _assert_bit_identical(_build_pair(exchange=exchange, wire=wire))


def test_pipelined_bit_identity_rng_selector():
    """randomk consumes per-chunk fold_in rng — the pipelined scan must
    reproduce the sequential batched rng stream exactly."""
    _assert_bit_identical(_build_pair(compressor="randomk"))


def test_pipelined_bit_identity_stateful_selector():
    """gaussian carries per-bucket threshold state through the scan."""
    _assert_bit_identical(_build_pair(compressor="gaussian"))


def test_pipelined_bit_identity_flat_opt():
    _assert_bit_identical(_build_pair(flat=True))


def test_pipelined_bit_identity_fused_ef():
    """The fused EF+select kernel path: uniform block-aligned chunks keep
    the pre-padded EF layout, so the pipelined scan runs the SAME fused
    kernel per chunk — parity must hold there too."""
    din, width = 64, 256
    params, loss_fn, make_batch = make_problem(din=din, width=width)
    density = 0.01
    spec0 = get_compressor("gaussian_fused", density=density)
    if spec0.fused_ef_fn is None:
        pytest.skip("fused EF kernel unavailable at this density")
    mesh = data_parallel_mesh()
    plan = plan_for_params(params, density, 8192, policy="uniform")
    assert plan.uniform and len(plan.buckets) >= 2
    batch = shard_batch(mesh, make_batch(64))
    outs = []
    for overlap in ("off", "auto"):
        spec = get_compressor("gaussian_fused", density=density)
        ts = build_dp_train_step(loss_fn, optax.sgd(0.05, momentum=0.9),
                                 spec, plan, mesh, overlap=overlap)
        state = ts.init_state(params, jax.random.PRNGKey(42))
        m = None
        for _ in range(3):
            state, m = ts.sparse_step(state, batch)
        outs.append((ts, state, m))
    _assert_bit_identical(outs)


# ------------------------------------------------------- eligibility gate

def test_ineligible_greedy_plan_falls_back_to_sequential():
    params, loss_fn, make_batch = make_problem()
    mesh = data_parallel_mesh()
    plan = plan_for_params(params, 0.25)          # greedy, non-uniform
    ts = build_dp_train_step(loss_fn, optax.sgd(0.05),
                             get_compressor("topk", density=0.25),
                             plan, mesh, overlap="auto")
    assert ts.overlap == "off"
    state = ts.init_state(params, jax.random.PRNGKey(42))
    state, m = ts.sparse_step(state, shard_batch(mesh, make_batch(64)))
    assert np.isfinite(float(m.loss))
    assert float(m.overlapped_bytes_sent) == 0.0


def test_ineligible_single_bucket_falls_back():
    params, loss_fn, make_batch = make_problem()
    mesh = data_parallel_mesh()
    # uniform policy, but one whole-model chunk -> nothing to overlap
    plan = plan_for_params(params, 0.25, 1 << 20, policy="uniform")
    assert len(plan.buckets) == 1
    ts = build_dp_train_step(loss_fn, optax.sgd(0.05),
                             get_compressor("topk", density=0.25),
                             plan, mesh, overlap="auto")
    assert ts.overlap == "off"


def test_overlap_off_is_sequential_and_validated():
    params, loss_fn, make_batch = make_problem()
    mesh = data_parallel_mesh()
    plan = plan_for_params(params, 0.25, 128, policy="uniform")
    ts = build_dp_train_step(loss_fn, optax.sgd(0.05),
                             get_compressor("topk", density=0.25),
                             plan, mesh, overlap="off")
    assert ts.overlap == "off"
    with pytest.raises(ValueError, match="overlap"):
        build_dp_train_step(loss_fn, optax.sgd(0.05),
                            get_compressor("topk", density=0.25),
                            plan, mesh, overlap="always")


# ------------------------------------------- elastic restore across geometry

def test_elastic_restore_across_overlap_geometry(tmp_path):
    """A checkpoint written under the pipelined schedule restores into a
    sequential build (and vice versa) — the schedule is a program
    property, not a state property, so params/EF must cross unchanged."""
    params, loss_fn, make_batch = make_problem()
    density = 0.25
    mesh = data_parallel_mesh()
    plan = plan_for_params(params, density, 128, policy="uniform")
    batch = shard_batch(mesh, make_batch(64))

    def build(overlap):
        ts = build_dp_train_step(loss_fn, optax.sgd(0.05, momentum=0.9),
                                 get_compressor("topk", density=density),
                                 plan, mesh, overlap=overlap)
        return ts, ts.init_state(params, jax.random.PRNGKey(42))

    for src, dst in (("auto", "off"), ("off", "auto")):
        ts_s, state = build(src)
        state, _ = ts_s.sparse_step(state, batch)
        assert np.abs(np.asarray(state.ef_residual)).sum() > 0
        path = save_checkpoint(str(tmp_path / f"ck_{src}"), state)
        ts_d, fresh = build(dst)
        restored = restore_checkpoint(path, fresh, ts_d.mesh)
        assert _leaves_equal(state.params, restored.params)
        assert np.array_equal(np.asarray(state.ef_residual),
                              np.asarray(restored.ef_residual))
        restored, m = ts_d.sparse_step(restored, batch)
        assert np.isfinite(float(m.loss))


# ------------------------------------------------------------ policy knob

def _ctx(**knobs):
    return RuleContext(knobs=knobs)


def test_overlap_promotion_rule_gates():
    rule = OverlapPromotionRule(min_bytes_per_step=1 << 20)
    snap = SignalSnapshot(step=100, bytes_per_step=float(2 << 20),
                          overlap="off")
    ok = _ctx(**{KNOB_OVERLAP: "off", KNOB_BUCKET: "uniform:8192"})
    d = rule.propose(snap, ok)
    assert d is not None and d.knob == KNOB_OVERLAP
    assert (d.old, d.new) == ("off", "auto")
    # knob already auto -> no-op
    assert rule.propose(snap, _ctx(**{KNOB_OVERLAP: "auto",
                                      KNOB_BUCKET: "uniform:8192"})) is None
    # non-uniform plan would recompile into the same sequential program
    assert rule.propose(snap, _ctx(**{KNOB_OVERLAP: "off",
                                      KNOB_BUCKET: "greedy:"})) is None
    # bytes below threshold
    low = SignalSnapshot(step=100, bytes_per_step=100.0, overlap="off")
    assert rule.propose(low, ok) is None
    # no sparse interval observed yet (overlap signal absent)
    cold = SignalSnapshot(step=100, bytes_per_step=float(2 << 20))
    assert rule.propose(cold, ok) is None


def test_signals_ingest_overlap_field():
    sig = PolicySignals(settle=0)
    assert sig.snapshot().overlap is None
    sig.update({"event": "train", "step": 5, "step_s": 0.1,
                "wire_format": "u16bf16", "overlap": "pipelined"})
    assert sig.snapshot().overlap == "pipelined"


def test_engine_treats_overlap_as_layout_change():
    """Applying (or reverting) an overlap decision must reset every
    selector arm's steady-state record and charge the recompile budget —
    the program layout changed, so old-layout timings are not comparable
    (ISSUE 7 satellite, mirroring the density/bucket-plan handling)."""
    d = PolicyDecision(step=30, rule="overlap_promotion",
                       knob=KNOB_OVERLAP, old="off", new="auto",
                       reason="test")
    eng = PolicyEngine([], knobs={KNOB_COMPRESSOR: "a",
                                  KNOB_OVERLAP: "off"},
                       signals=PolicySignals(settle=0))
    eng.emit({"event": "train", "step": 10, "step_s": 0.05})   # dense ref
    eng.emit({"event": "train", "step": 20, "step_s": 0.1,
              "wire_format": "u16bf16"})                       # arm record
    assert "a" in eng.signals.snapshot().arm_step_s
    before = eng.recompiles
    eng.note_applied(d)
    snap = eng.signals.snapshot()
    assert "a" not in snap.arm_step_s          # old-layout record dropped
    assert snap.dense_step_s_ema is not None   # dense reference survives
    assert eng.recompiles == before + 1
    # the revert twin is charged the same way
    eng.emit({"event": "train", "step": 40, "step_s": 0.1,
              "wire_format": "u16bf16"})
    assert "a" in eng.signals.snapshot().arm_step_s
    eng.note_reverted(d.reversed(step=50, reason="probation"))
    assert "a" not in eng.signals.snapshot().arm_step_s
    assert eng.recompiles == before + 2
