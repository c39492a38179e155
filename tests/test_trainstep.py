"""Tests for the fused DP train step (SURVEY.md §4 implication (b)).

All run on the virtual 8-device CPU mesh from conftest.py — the multi-worker
testing the reference could never do without a cluster (SURVEY.md §4 item 4).
"""

import inspect
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.flatten_util import ravel_pytree

from gaussiank_sgd_tpu.compressors import get_compressor
from gaussiank_sgd_tpu.parallel.bucketing import (make_bucket_plan,
                                                  plan_for_params)
from gaussiank_sgd_tpu.parallel.mesh import (data_parallel_mesh,
                                             hierarchical_dp_mesh,
                                             shard_batch)
from gaussiank_sgd_tpu.parallel.trainstep import build_dp_train_step


def make_problem(din=16, dout=4, width=32, seed=0):
    """A 2-layer MLP regression problem, deterministic."""
    k = jax.random.PRNGKey(seed)
    k1, k2, kx, kw = jax.random.split(k, 4)
    params = {
        "w1": jax.random.normal(k1, (din, width)) * 0.1,
        "b1": jnp.zeros((width,)),
        "w2": jax.random.normal(k2, (width, dout)) * 0.1,
        "b2": jnp.zeros((dout,)),
    }
    w_true = jax.random.normal(kw, (din, dout))

    def loss_fn(p, mstate, batch, rng):
        x, y = batch
        h = jnp.tanh(x @ p["w1"] + p["b1"])
        pred = h @ p["w2"] + p["b2"]
        mse = jnp.mean((pred - y) ** 2)
        return mse, (mstate, {"mse": mse})

    def make_batch(n, seed=1):
        kx2 = jax.random.PRNGKey(seed)
        x = jax.random.normal(kx2, (n, din))
        return (x, x @ w_true)

    return params, loss_fn, make_batch


def build(compressor="topk", density=0.25, bucket_size=None, mesh=None,
          lr=0.05, momentum=0.9, **kw):
    params, loss_fn, make_batch = make_problem()
    mesh = mesh or data_parallel_mesh()
    spec = get_compressor(compressor, density=density)
    plan = plan_for_params(params, density, bucket_size)
    opt = optax.sgd(lr, momentum=momentum)
    ts = build_dp_train_step(loss_fn, opt, spec, plan, mesh, **kw)
    state = ts.init_state(params, jax.random.PRNGKey(42))
    return ts, state, make_batch, mesh


def test_dense_step_runs_and_loss_decreases():
    ts, state, make_batch, mesh = build("topk")
    batch = shard_batch(mesh, make_batch(64))
    losses = []
    for _ in range(20):
        state, m = ts.dense_step(state, batch)
        losses.append(float(m.loss))
    assert losses[-1] < losses[0] * 0.5


def test_sparse_full_density_matches_dense():
    """density=1.0 topk sparse path == dense psum path (SURVEY §4 (b))."""
    params, loss_fn, make_batch = make_problem()
    mesh = data_parallel_mesh()
    opt = optax.sgd(0.05, momentum=0.9)
    spec = get_compressor("topk", density=1.0)
    plan = plan_for_params(params, 1.0)
    # wire="off": dense==sparse equality at rtol 1e-5 needs the exchange
    # values untouched; the bf16 wire would add ~2^-8 relative error
    ts = build_dp_train_step(loss_fn, opt, spec, plan, mesh, wire="off")
    batch = shard_batch(mesh, make_batch(64))

    s_dense = ts.init_state(params, jax.random.PRNGKey(0))
    s_sparse = ts.init_state(params, jax.random.PRNGKey(0))
    for _ in range(5):
        s_dense, _ = ts.dense_step(s_dense, batch)
        s_sparse, _ = ts.sparse_step(s_sparse, batch)
    fd, _ = ravel_pytree(s_dense.params)
    fs, _ = ravel_pytree(s_sparse.params)
    np.testing.assert_allclose(np.asarray(fd), np.asarray(fs),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("compressor", ["topk", "approxtopk", "approxtopk16",
                                        "gaussian", "gaussian_warm",
                                        "randomkec",
                                        "dgcsampling", "redsync",
                                        "redsynctrim"])
def test_sparse_step_converges(compressor):
    """EF-sparsified training at 10% density still optimizes (SURVEY §2.3).

    momentum=0: randomk's sparse stochastic updates diverge under heavy
    momentum on this tiny problem; plain EF-SGD is the paper setting.
    """
    ts, state, make_batch, mesh = build(compressor, density=0.10,
                                        momentum=0.0)
    batch = shard_batch(mesh, make_batch(64))
    losses = []
    for _ in range(60):
        state, m = ts.sparse_step(state, batch)
        losses.append(float(m.loss))
    assert losses[-1] < losses[0] * 0.5, losses[-1]


def test_warmup_then_sparse_transition():
    ts, state, make_batch, mesh = build("gaussian", density=0.05)
    batch = shard_batch(mesh, make_batch(64))
    for i in range(5):
        state, m = ts.dense_step(state, batch)
    assert int(state.step) == 5
    assert float(jnp.abs(state.ef_residual).sum()) == 0.0  # untouched in warmup
    for i in range(10):
        state, m = ts.sparse_step(state, batch)
    assert int(state.step) == 15
    assert float(jnp.abs(state.ef_residual).sum()) > 0.0   # EF now carrying


def test_ef_residual_carries_unsent_mass():
    """After one sparse step: residual + sent == acc (elementwise split)."""
    ts, state, make_batch, mesh = build("topk", density=0.1, momentum=0.0,
                                        lr=1.0)
    batch = shard_batch(mesh, make_batch(8))
    # With P workers seeing identical per-shard batches? They don't — batch is
    # sharded. Instead verify conservation: acc == residual' + contribution,
    # using the public pieces directly on one shard's grad.
    import gaussiank_sgd_tpu.compressors as C
    g = jax.random.normal(jax.random.PRNGKey(3), (1000,))
    res0 = jax.random.normal(jax.random.PRNGKey(4), (1000,)) * 0.01
    acc = res0 + g
    out = C.topk_compress(acc, 100)
    sent = C.decompress(out.compressed, 1000)
    np.testing.assert_allclose(np.asarray(sent + out.residual),
                               np.asarray(acc), rtol=1e-6)


def test_bucketed_matches_semantics_and_converges():
    ts, state, make_batch, mesh = build("gaussian", density=0.1,
                                        bucket_size=256)
    assert len(ts.plan.buckets) > 1
    batch = shard_batch(mesh, make_batch(64))
    losses = []
    for _ in range(40):
        state, m = ts.sparse_step(state, batch)
        losses.append(float(m.loss))
    assert losses[-1] < losses[0] * 0.5


def test_per_tensor_buckets():
    plan = make_bucket_plan([100, 5, 200], 0.1, bucket_size=0)
    assert [b.size for b in plan.buckets] == [100, 5, 200]
    assert [b.k for b in plan.buckets] == [10, 1, 20]
    plan2 = make_bucket_plan([100, 5, 200], 0.1, bucket_size=150)
    assert [b.size for b in plan2.buckets] == [305] or \
           [b.size for b in plan2.buckets] == [205, 100]  # greedy merge
    plan3 = make_bucket_plan([100, 5, 200], 0.1, bucket_size=None)
    assert [b.size for b in plan3.buckets] == [305]


def test_hierarchical_mesh_sparse_step():
    """2x4 (dcn, ici) mesh: sparse gather on ici, dense psum over dcn."""
    mesh = hierarchical_dp_mesh(ici_size=4, dcn_size=2)
    ts, state, make_batch, _ = build("gaussian", density=0.1, mesh=mesh)
    batch = shard_batch(mesh, make_batch(64))
    losses = []
    for _ in range(40):
        state, m = ts.sparse_step(state, batch)
        losses.append(float(m.loss))
    assert losses[-1] < losses[0] * 0.5


def test_microbatch_accumulation_matches_big_batch():
    """nsteps_update=4 over the same data == single big batch (dense path)."""
    params, loss_fn, make_batch = make_problem()
    mesh = data_parallel_mesh()
    opt = optax.sgd(0.05)
    spec = get_compressor("topk", density=1.0)
    plan = plan_for_params(params, 1.0)
    ts1 = build_dp_train_step(loss_fn, opt, spec, plan, mesh,
                              num_microbatches=1)
    ts4 = build_dp_train_step(loss_fn, opt, spec, plan, mesh,
                              num_microbatches=4)
    batch = shard_batch(mesh, make_batch(64))
    s1 = ts1.init_state(params, jax.random.PRNGKey(0))
    s4 = ts4.init_state(params, jax.random.PRNGKey(0))
    s1, m1 = ts1.dense_step(s1, batch)
    s4, m4 = ts4.dense_step(s4, batch)
    f1, _ = ravel_pytree(s1.params)
    f4, _ = ravel_pytree(s4.params)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f4),
                               rtol=1e-4, atol=1e-6)


def test_fold_lr_variant():
    """fold_lr: EF carries lr-scaled grads, inner opt has unit lr."""
    params, loss_fn, make_batch = make_problem()
    mesh = data_parallel_mesh()
    sched = lambda step: 0.05
    spec = get_compressor("gaussian", density=0.1)
    plan = plan_for_params(params, 0.1)
    ts = build_dp_train_step(loss_fn, optax.sgd(1.0, momentum=0.9), spec,
                             plan, mesh, fold_lr=sched)
    state = ts.init_state(params, jax.random.PRNGKey(0))
    batch = shard_batch(mesh, make_batch(64))
    losses = []
    for _ in range(60):
        state, m = ts.sparse_step(state, batch)
        losses.append(float(m.loss))
    assert losses[-1] < losses[0] * 0.5


def test_grad_clipping():
    ts, state, make_batch, mesh = build("topk", density=0.5, clip_norm=0.01)
    batch = shard_batch(mesh, make_batch(64))
    state, m = ts.dense_step(state, batch)
    assert float(m.grad_norm) <= 0.0101


def test_metrics_fields():
    # this tiny single-bucket f32 plan is wire-eligible (parallel/wire.py),
    # so the exchange moves one packed u32 word per entry
    ts, state, make_batch, mesh = build("gaussian", density=0.1)
    batch = shard_batch(mesh, make_batch(64))
    state, m = ts.sparse_step(state, batch)
    assert ts.wire_format == "u16bf16"
    assert m.bytes_sent.dtype == jnp.float32  # f32: no int32 wrap at scale
    assert int(m.bytes_sent) == ts.plan.total_k * 4
    assert int(m.num_selected) >= 0


def test_metrics_fields_wire_off():
    # wire="off" keeps the legacy i32+f32 pair: 8 bytes per entry
    ts, state, make_batch, mesh = build("gaussian", density=0.1, wire="off")
    batch = shard_batch(mesh, make_batch(64))
    state, m = ts.sparse_step(state, batch)
    assert ts.wire_format == "i32f32"
    assert int(m.bytes_sent) == ts.plan.total_k * 8


def test_flat_opt_matches_optax_trajectory():
    """The flat sparse-aware SGD+momentum update (parallel/flat_opt.py)
    must produce the SAME parameter trajectory as the optax path — sparse
    steps, dense warm-up steps, and a dense->sparse transition — for both
    plain momentum and momentum+weight-decay.

    Both builds exchange in f32 (``wire="off"``), so the tolerance below is
    the f32 bound it looks like: the two updates are the same algebra in a
    different operation order, a few ulp (~1e-8 on these O(0.1) params) per
    step over 8 steps. Under the packed wire the comparison is not an f32
    one: a sent value sitting on a bf16 rounding midpoint rounds the other
    way after a 1-ulp perturbation, which moves that parameter by
    lr * 2^-8 * |v| / P in one step (EF returns it the next) — 2.4e-5 here."""
    from gaussiank_sgd_tpu.parallel.flat_opt import FlatSGDM

    for wd in (0.0, 0.01):
        params, loss_fn, make_batch = make_problem()
        mesh = data_parallel_mesh()
        spec = get_compressor("topk", density=0.25)
        plan = plan_for_params(params, 0.25, None)
        chain = []
        if wd:
            chain.append(optax.add_decayed_weights(wd))
        chain.append(optax.sgd(0.05, momentum=0.9))
        ts_ref = build_dp_train_step(loss_fn, optax.chain(*chain), spec,
                                     plan, mesh, wire="off")
        ts_flat = build_dp_train_step(
            loss_fn, None, spec, plan, mesh, wire="off",
            flat_opt=FlatSGDM(lr=0.05, momentum=0.9, weight_decay=wd))
        s_ref = ts_ref.init_state(params, jax.random.PRNGKey(42))
        s_flat = ts_flat.init_state(params, jax.random.PRNGKey(42))
        batch = shard_batch(mesh, make_batch(64))
        for i in range(3):                       # dense warm-up
            s_ref, _ = ts_ref.dense_step(s_ref, batch)
            s_flat, _ = ts_flat.dense_step(s_flat, batch)
        for i in range(5):                       # sparse (EF + momentum)
            s_ref, m_ref = ts_ref.sparse_step(s_ref, batch)
            s_flat, m_flat = ts_flat.sparse_step(s_flat, batch)
        for kname in params:
            np.testing.assert_allclose(
                np.asarray(s_flat.params[kname]),
                np.asarray(s_ref.params[kname]), rtol=1e-5, atol=1e-6,
                err_msg=f"wd={wd} param {kname}")
        np.testing.assert_allclose(float(m_flat.loss), float(m_ref.loss),
                                   rtol=1e-5)


def test_flat_opt_matches_optax_gtopk():
    """Same trajectory equivalence over the gTop-k butterfly exchange —
    the fused path rebinds (idx, val) to the globally-selected,
    /P-pre-averaged pairs (trainstep gtopk branch)."""
    from gaussiank_sgd_tpu.parallel.flat_opt import FlatSGDM

    params, loss_fn, make_batch = make_problem()
    mesh = data_parallel_mesh()
    spec = get_compressor("topk", density=0.25)
    plan = plan_for_params(params, 0.25, None)
    ts_ref = build_dp_train_step(loss_fn, optax.sgd(0.05, momentum=0.9),
                                 spec, plan, mesh, exchange="gtopk")
    ts_flat = build_dp_train_step(
        loss_fn, None, spec, plan, mesh, exchange="gtopk",
        flat_opt=FlatSGDM(lr=0.05, momentum=0.9))
    s_ref = ts_ref.init_state(params, jax.random.PRNGKey(42))
    s_flat = ts_flat.init_state(params, jax.random.PRNGKey(42))
    batch = shard_batch(mesh, make_batch(64))
    for _ in range(4):
        s_ref, m_ref = ts_ref.sparse_step(s_ref, batch)
        s_flat, m_flat = ts_flat.sparse_step(s_flat, batch)
    for kname in params:
        np.testing.assert_allclose(np.asarray(s_flat.params[kname]),
                                   np.asarray(s_ref.params[kname]),
                                   rtol=1e-5, atol=1e-6)


def test_fused_ef_path_active_and_matches_unfused():
    """gaussian_fused + allgather + single bucket must take the fused
    EF+select path (padded ef_numel) and track the unfused program's
    trajectory to accumulate-rounding tolerance (the kernel may FMA the
    res + scale*g accumulate)."""
    params, loss_fn, make_batch = make_problem()
    mesh = data_parallel_mesh()
    spec = get_compressor("gaussian_fused", density=0.01)
    plan = plan_for_params(params, 0.01)
    n_total = plan.total_numel

    ts_f = build_dp_train_step(loss_fn, optax.sgd(0.05), spec, plan, mesh)
    assert ts_f.ef_numel > n_total            # padded: fused path active
    # same compressor with the fused form masked off -> unfused reference
    spec_u = spec._replace(fused_ef_fn=None, ef_pad=None)
    ts_u = build_dp_train_step(loss_fn, optax.sgd(0.05), spec_u, plan, mesh)
    assert ts_u.ef_numel == n_total

    batch = shard_batch(mesh, make_batch(64))
    sf = ts_f.init_state(params, jax.random.PRNGKey(42))
    su = ts_u.init_state(params, jax.random.PRNGKey(42))
    for _ in range(8):
        sf, mf = ts_f.sparse_step(sf, batch)
        su, mu = ts_u.sparse_step(su, batch)
    pf, _ = ravel_pytree(sf.params)
    pu, _ = ravel_pytree(su.params)
    np.testing.assert_allclose(np.asarray(pf), np.asarray(pu),
                               rtol=2e-5, atol=2e-6)
    assert float(mf.num_selected) == pytest.approx(
        float(mu.num_selected), rel=0.1)
    # pad region of every worker's padded row stays exactly zero
    ef = np.asarray(sf.ef_residual).reshape(mesh.size, ts_f.ef_numel)
    assert not ef[:, n_total:].any()
    # and the unpadded prefix matches the unfused residual to rounding
    ef_u = np.asarray(su.ef_residual).reshape(mesh.size, n_total)
    np.testing.assert_allclose(ef[:, :n_total], ef_u, rtol=2e-5, atol=2e-6)


def test_fused_ef_guard_skip_bit_identity():
    """A non-finite batch through the FUSED path must commit the old
    params/opt/EF bit-identically (padded buffer included) while step/rng
    advance — the guard contract is layout-independent."""
    params, loss_fn, make_batch = make_problem()
    mesh = data_parallel_mesh()
    spec = get_compressor("gaussian_fused", density=0.01)
    plan = plan_for_params(params, 0.01)
    ts = build_dp_train_step(loss_fn, optax.sgd(0.05), spec, plan, mesh)
    state = ts.init_state(params, jax.random.PRNGKey(42))
    batch = shard_batch(mesh, make_batch(64))
    for _ in range(3):                   # build up a nonzero residual
        state, _m = ts.sparse_step(state, batch)
    before_params = np.asarray(ravel_pytree(state.params)[0])
    before_ef = np.asarray(state.ef_residual)
    before_step = int(state.step)
    x, y = make_batch(64)
    bad = shard_batch(mesh, (x.at[0, 0].set(jnp.nan), y))
    state, m = ts.sparse_step(state, bad)
    assert float(m.skipped) == 1.0 and float(m.nonfinite) > 0
    assert int(state.step) == before_step + 1
    assert np.array_equal(np.asarray(ravel_pytree(state.params)[0]),
                          before_params)
    assert np.array_equal(np.asarray(state.ef_residual), before_ef)


def test_gtopk_and_bf16_fall_back_to_unfused():
    """Build-time eligibility: gtopk (needs the materialized accumulator)
    and non-f32 grad dtypes must keep the unfused path."""
    params, loss_fn, make_batch = make_problem()
    mesh = data_parallel_mesh()
    spec = get_compressor("gaussian_fused", density=0.01)
    plan = plan_for_params(params, 0.01)
    ts_g = build_dp_train_step(loss_fn, optax.sgd(0.05), spec, plan, mesh,
                               exchange="gtopk")
    assert ts_g.ef_numel == plan.total_numel
    ts_b = build_dp_train_step(loss_fn, optax.sgd(0.05), spec, plan, mesh,
                               grad_dtype=jnp.bfloat16)
    assert ts_b.ef_numel == plan.total_numel


def test_decorrelate_comp_rng_spreads_random_indices():
    """Satellite (VERDICT r5 weak #6): with the shared compressor seed all
    8 workers draw the SAME randomkec indices, so one step touches ~k
    coordinates; decorrelated seeds touch ~8x more. The flag must change
    exactly that and nothing else about the program."""
    params, loss_fn, make_batch = make_problem()
    mesh = data_parallel_mesh()
    spec = get_compressor("randomkec", density=0.05)
    plan = plan_for_params(params, 0.05)

    def run(decorrelate):
        ts = build_dp_train_step(loss_fn, optax.sgd(0.5), spec, plan, mesh,
                                 decorrelate_comp_rng=decorrelate)
        state = ts.init_state(params, jax.random.PRNGKey(42))
        batch = shard_batch(mesh, make_batch(64))
        new_state, _m = ts.sparse_step(state, batch)
        p0, _ = ravel_pytree(params)
        p1, _ = ravel_pytree(new_state.params)
        return int(np.sum(np.asarray(p0) != np.asarray(p1)))

    shared = run(False)
    spread = run(True)
    assert spread > 2 * shared


def test_kernel_mode_comes_from_the_mesh_not_the_default_backend(monkeypatch):
    """A step's Pallas kernels run the way its MESH's platform needs, decided
    once at build time and reported on DPTrainStep.kernel_mode. With the
    process's default backend claiming to be a TPU, a step built over the CPU
    mesh must still carry the interpreted kernel (a Mosaic call cannot even
    lower for the CPU devices it would run on)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ts, state, make_batch, mesh = build("gaussian_fused", density=0.01)
    assert ts.kernel_mode == "interpret"
    batch = shard_batch(mesh, make_batch(64))
    assert "tpu_custom_call" not in ts.sparse_step.lower(state,
                                                         batch).as_text()
    state, m = ts.sparse_step(state, batch)
    assert np.isfinite(float(m.loss))
    assert build("topk")[0].kernel_mode == "none"      # no kernel at all


@pytest.fixture
def v5e(monkeypatch):
    """A device-less v5e:2x2 (libtpu compiles for a chip this host does not
    have); the tests that compile for it live in this ONE file."""
    pytest.importorskip("libtpu")
    from jax.experimental import topologies

    # no metadata server to ask, and no chip to guard with libtpu's
    # one-process lockfile: nothing here creates a TPU client
    monkeypatch.setenv("TPU_SKIP_MDS_QUERY", "1")
    monkeypatch.setenv("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    try:
        return topologies.get_topology_desc("v5e:2x2", "tpu")
    except jax.errors.JaxRuntimeError as e:
        pytest.skip(f"no device-less TPU topology on this host: {e}")


def test_tpu_mesh_never_gets_an_interpreted_kernel(v5e):
    """The converse, on a device-less TPU topology: default backend CPU,
    mesh TPU — the step reports ``mosaic`` and lowers to exactly one Mosaic
    call. Before PR 21 the interpreted kernel was inlined into the TPU
    program, silently."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(v5e.devices[:2]), ("dp",))
    assert jax.default_backend() == "cpu"
    params, loss_fn, make_batch = make_problem()
    ts = build_dp_train_step(
        loss_fn, optax.sgd(0.05), get_compressor("gaussian_fused",
                                                 density=0.01),
        plan_for_params(params, 0.01, None), mesh)
    assert ts.kernel_mode == "mosaic"
    state = jax.eval_shape(
        lambda p: ts.init_state(p, jax.random.PRNGKey(0)), params)
    batch = jax.eval_shape(lambda: make_batch(64))
    text = ts.sparse_step.lower(state, batch).as_text()
    assert text.count("tpu_custom_call") == 1


@pytest.mark.parametrize("kv_heads,group,d,dv,window", [
    (32, 1, 192, 128, None), (4, 8, 128, 128, None), (8, 4, 64, 64, None),
    (4, 8, 128, 128, 1024), (4, 8, 128, 128, 2048), (2, 8, 256, 256, None)],
    ids=["joyai_mla_dp1", "full_128", "lfm2_conv_dp1", "window_1024",
         "window_2048", "qwen3next_gdn_dp1"])
def test_the_attention_kernels_compile_at_the_cells_shapes(
        v5e, kv_heads, group, d, dv, window):
    """The tiles that `models/blocks/attention.splash_sizes` computes fit the
    kernels' 16 MiB of VMEM at the four transformer cells' shapes, forward
    and backward: Mosaic's own verdict, which lowering does not ask for
    (a full layer's fused backward kernel at 512 query rows, 2048 keys and
    heads of 192 was refused by 76 KiB)."""
    from jax.sharding import SingleDeviceSharding

    from gaussiank_sgd_tpu.models.blocks import attention

    def both(q, k, v, do):
        out, back = jax.vjp(
            lambda *qkv: attention.splash_attention(*qkv, window), q, k, v)
        return out, back(do)

    one_chip = SingleDeviceSharding(v5e.devices[0])
    b, s = 2, 8192
    avals = [jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
             for shape in ((b, s, kv_heads, group, d), (b, s, kv_heads, d),
                           (b, s, kv_heads, dv), (b, s, kv_heads, group, dv))]
    text = jax.jit(both).lower(*avals).compile().as_text()
    assert ("dq_no_residuals" in text) == bool(window)
    assert "dkv_no_residuals" in text and "fwd_residuals" in text


def _each_kernel_compiles_once(fn, shapes, device, names):
    """`fn` compiled for `device` at `shapes` ((shape, dtype), ...): each of
    `names` is one custom call of the executable."""
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(device)
    avals = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
             for shape, dtype in shapes]
    text = jax.jit(fn).lower(*avals).compile().as_text()
    for name in names:
        assert len(re.findall(rf"%\S*{name}[_.\d]* = ", text)) == 1, name


def test_the_delta_rule_kernels_compile_at_the_cells_shape(v5e):
    """`ops/delta_rule.py`'s three kernels at `qwen3next_gdn_dp1`'s shape (2
    x 8192 tokens, 16 key and 32 value heads of 128, bfloat16), sixteen
    chunks a grid step in rounds of eight: Mosaic's own verdict on the
    permutation of lanes that the inverse's substitution makes
    (`take_along_axis`), on the joins' two chunks side by side along the
    lanes and on the VMEM the calls ask for (`vmem_limit_bytes` from the
    blocks and a round's spills, under a core's 128 MiB), which neither
    lowering nor the interpreter gives."""
    from gaussiank_sgd_tpu.ops import delta_rule

    def both(q, k, v, g, beta, do, dstate):
        def rule(*args):
            return delta_rule.gated_delta_rule(*args, d)
        out, back = jax.vjp(rule, q, k, v, g, beta)
        return rule(q, k, v, g, beta), out, back((do, dstate))

    b, s, hk, h, d = 2, 8192, 16, 32, 128
    assert delta_rule.chunks_a_step(s // delta_rule.CHUNK) == 16
    for backward in (False, True):
        assert delta_rule.vmem_bytes(16, h // hk, d, d, 2,
                                     backward) < 48 * 2 ** 20
    _each_kernel_compiles_once(
        both, (((b, s, hk * d), jnp.bfloat16), ((b, s, hk * d), jnp.bfloat16),
               ((b, s, h * d), jnp.bfloat16), ((b, s, h), jnp.float32),
               ((b, s, h), jnp.float32), ((b, s, h * d), jnp.bfloat16),
               ((b, h, d, d), jnp.float32)),
        v5e.devices[0], ("gdn_fwd", "gdn_fwd_kept", "gdn_bwd"))


def test_the_scan_kernels_compile_at_the_cells_shape(v5e):
    """`ops/ssd_scan.py`'s three kernels at `nemotronh_ssd_dp1`'s shape (2 x
    8192 tokens, 64 heads of 64 in 8 groups, a state of 128, bfloat16, `[x |
    B | C]` 6144 wide), eight chunks a grid step: Mosaic's own verdict on
    the products whose left side is transposed (a float32 row's pieces
    against zeros and ones), on the group's columns of `[x | B | C]` by the
    index maps and on the VMEM the calls ask for (`vmem_limit_bytes` from
    the blocks, under a core's 128 MiB), which neither lowering nor the
    interpreter gives."""
    from gaussiank_sgd_tpu.ops import ssd_scan

    b, s, h, p, g, n = 2, 8192, 64, 64, 8, 128

    def both(xbc, dt, a, dy, dstate):
        def scan(*args):
            return ssd_scan.ssd_scan(*args, g, n)
        out, back = jax.vjp(scan, xbc, dt, a)
        return scan(xbc, dt, a), out, back((dy, dstate))

    assert ssd_scan.takes((b, s, h, p), (b, s, g, n))
    _each_kernel_compiles_once(
        both, (((b, s, h * p + 2 * g * n), jnp.bfloat16),
               ((b, s, h), jnp.float32), ((h,), jnp.float32),
               ((b, s, h * p), jnp.bfloat16), ((b, h, n, p), jnp.float32)),
        v5e.devices[0], ("ssd_fwd", "ssd_fwd_kept", "ssd_bwd"))


def test_the_prologue_kernels_compile_at_the_cells_shape(v5e):
    """`ops/delta_prologue.py`'s two kernels at `qwen3next_gdn_dp1`'s shape
    (2 x 8192 positions, the first 8192 of `qkvz`'s 12288 columns where they
    lie, 256 positions a grid step): Mosaic's own verdict on the loads at a
    sublane offset that are the shifts along the sequence, on the dynamic
    lane offset of a head in the loop over heads and on the VMEM the calls
    ask for (`vmem_limit_bytes` from the blocks, under a core's 128 MiB),
    which neither lowering nor the interpreter gives."""
    from gaussiank_sgd_tpu.ops import delta_prologue

    b, s, keys, values, dk, taps = 2, 8192, 2048, 4096, 128, 4
    width = 2 * keys + values

    def both(qkvz, taps, dq, dk_, dv):
        out, back = jax.vjp(
            lambda x, t: delta_prologue.conv_norm(x, t, keys, dk), qkvz, taps)
        return out, back((dq, dk_, dv))

    assert delta_prologue.takes(s, dk, 128, taps)
    assert delta_prologue.rows_a_step(s) == 256
    for backward in (False, True):
        assert delta_prologue.vmem_bytes(256, width, 2, taps,
                                         backward) < 48 * 2 ** 20
    _each_kernel_compiles_once(
        both, (((b, s, width + values), jnp.bfloat16),
               ((width, taps), jnp.float32), ((b, s, keys), jnp.bfloat16),
               ((b, s, keys), jnp.bfloat16), ((b, s, values), jnp.bfloat16)),
        v5e.devices[0], ("gdn_conv_fwd", "gdn_conv_bwd"))


def test_init_state_is_created_under_the_steps_shardings():
    """Replicated leaves on every device of the mesh, per-worker leaves one
    shard per worker — so the first step neither re-lays the state out nor
    compiles for a layout it will never see again (an unplaced state cost a
    second compile of the step program: ~30 s for VGG-16 on the chip)."""
    ts, state, make_batch, mesh = build("gaussian_warm")
    devs = set(mesh.devices.flat)
    n = ts.plan.total_numel
    assert [s.data.shape for s in state.ef_residual.addressable_shards] \
        == [(n,)] * mesh.size
    assert {s.device for s in state.ef_residual.addressable_shards} == devs
    assert [s.data.shape for s in state.comp_state.addressable_shards] \
        == [(1, 1)] * mesh.size
    for leaf in jax.tree_util.tree_leaves(
            (state.step, state.params, state.opt_state, state.rng)):
        assert leaf.sharding.is_fully_replicated
        assert set(leaf.sharding.device_set) == devs
    batch = shard_batch(mesh, make_batch(64))
    for _ in range(2):
        state, _ = ts.dense_step(state, batch)
    assert ts.dense_step._cache_size() == 1


def test_microbatch_divisibility_asserts():
    """--nsteps-update must divide the per-worker batch (VERDICT r3
    item 8): a clear ValueError, not a reshape error deep in jit."""
    from gaussiank_sgd_tpu.parallel.trainstep import _microbatch_grads

    def loss_fn(params, mstate, batch, rng):
        return jnp.sum(params["w"] * batch[0].sum()), (mstate, {})

    with pytest.raises(ValueError, match="not divisible"):
        _microbatch_grads(loss_fn, {"w": jnp.ones(())}, {},
                          (jnp.ones((10, 2)), jnp.ones((10,))),
                          None, num_microbatches=3)


def test_the_bundle_holds_the_two_programs_the_trainer_dispatches():
    """``DPTrainStep`` carries ``sparse_step`` and ``dense_step`` and no
    other program: no timing twin, probe or multi-step builder rides the
    bundle (they doubled the sparse program's variants)."""
    ts, state, make_batch, mesh = build("topk")
    programs = {f for f in ts._fields if callable(getattr(ts, f))
                and hasattr(getattr(ts, f), "lower")}
    assert programs == {"sparse_step", "dense_step"}
    builders = {f for f in ts._fields
                if inspect.isfunction(getattr(ts, f))}
    assert builders == {"init_state"}


@pytest.mark.parametrize("exchange,collective", [
    ("allgather", "all_gather"), ("gtopk", "collective_permute")])
def test_exchange_scope_is_on_the_collective(exchange, collective):
    """The payload collective of a two-worker sparse program is lowered
    under the ``exchange`` scope — the real collective, which a device
    trace books as exchange time — and so is the dense program's psum."""
    ts, state, make_batch, mesh = build(
        "topk", mesh=data_parallel_mesh(2), exchange=exchange)
    batch = shard_batch(mesh, make_batch(8))
    txt = ts.sparse_step.lower(state, batch).as_text(debug_info=True)
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', txt, re.M))
    ops = [line for line in txt.splitlines()
           if f'"stablehlo.{collective}"' in line]
    assert ops, f"no {collective} in the two-worker sparse program"
    for line in ops:
        name = locs[re.search(r"loc\((#loc\d+)\)", line).group(1)]
        assert name.startswith("exchange/"), name
    dense = ts.dense_step.lower(state, batch).as_text(debug_info=True)
    assert 'loc("exchange/psum"' in dense


# ---------------------------------------------------------------------------
# the step is committed once (PR 32): the guard decides before the update,
# one lax.cond holds everything after the exchange, nothing is selected
# ---------------------------------------------------------------------------

def _walk_eqns(jaxpr):
    """(equation, the jaxpr that holds it) over the whole nested jaxpr."""
    from gaussiank_sgd_tpu.lint.program_audit import _sub_jaxprs
    for eqn in jaxpr.eqns:
        yield eqn, jaxpr
        for sub in _sub_jaxprs(eqn):
            yield from _walk_eqns(sub)


def _build_commit(path, workers, wd=0.01):
    from gaussiank_sgd_tpu.parallel.flat_opt import FlatSGDM
    params, loss_fn, make_batch = make_problem()
    mesh = data_parallel_mesh(workers)
    spec = get_compressor("gaussian_fused", density=0.05)
    plan = plan_for_params(params, 0.05)
    if path == "flat":
        ts = build_dp_train_step(
            loss_fn, None, spec, plan, mesh, wire="off",
            flat_opt=FlatSGDM(lr=0.05, momentum=0.9, weight_decay=wd))
    else:
        opt = optax.sgd(optax.constant_schedule(0.05), momentum=0.9)
        ts = build_dp_train_step(loss_fn, opt, spec, plan, mesh, wire="off")
    state = ts.init_state(params, jax.random.PRNGKey(42))
    return ts, state, make_batch, mesh, plan.total_numel


@pytest.mark.parametrize("path", ["flat", "optax"])
@pytest.mark.parametrize("workers", [1, 2])
@pytest.mark.parametrize("program", ["sparse", "dense"])
def test_the_step_is_committed_by_one_cond_and_no_select(program, workers,
                                                         path):
    """Form (a) of ISSUE 32, pinned on the step's jaxpr (a count of work):
    ONE ``cond`` returns the state's n-length leaves, none of its branches
    holds a collective (every worker takes the same side: ``ok`` is the
    psum'd count), no n-length ``select_n`` on a broadcast scalar predicate
    — the old ``where(ok, new, old)`` — is left anywhere, and on the flat
    path ``-lr*m'`` is no vector of its own (no n-length product is cut
    into the leaves' pieces)."""
    from gaussiank_sgd_tpu.lint.program_audit import (collect_primitives,
                                                      collective_inventory)
    ts, state, make_batch, mesh, n = _build_commit(path, workers)
    step = ts.sparse_step if program == "sparse" else ts.dense_step
    closed = jax.make_jaxpr(step)(state, shard_batch(mesh, make_batch(16)))
    eqns = list(_walk_eqns(closed.jaxpr))

    def size(v):
        return int(np.prod(v.aval.shape)) if hasattr(v.aval, "shape") else 0

    commits = [e for e, _ in eqns if e.primitive.name == "cond"
               and any(size(v) >= n for v in e.outvars)]
    assert len(commits) == 1, [str(e.primitive) for e in commits]
    for branch in commits[0].params["branches"]:
        held = collective_inventory(collect_primitives(branch.jaxpr))
        assert not held, held
    for e, jaxpr in eqns:
        if e.primitive.name != "select_n" or size(e.outvars[0]) < n:
            continue
        made = [p for p in jaxpr.eqns if e.invars[0] in p.outvars]
        scalar = size(e.invars[0]) == 1 or (
            made and made[0].primitive.name == "broadcast_in_dim"
            and size(made[0].invars[0]) == 1)
        assert not scalar, (
            "an n-length select on a scalar predicate: the old commit")
    if path == "flat":
        cut = {"slice", "dynamic_slice", "split", "reshape", "gather"}
        for e, jaxpr in eqns:
            if e.primitive.name == "mul" and size(e.outvars[0]) == n:
                users = {u.primitive.name for u in jaxpr.eqns
                         if e.outvars[0] in u.invars}
                assert not users & cut, (
                    f"an n-length product is cut into leaves: {users}")


def _with_negative_zeros(state, mesh):
    """The state with -0.0 planted in every float optimizer leaf and in the
    residual: an added +0.0 would flip them, a step left alone does not."""
    def plant(x):
        if not jnp.issubdtype(x.dtype, jnp.floating) or x.size < 4:
            return x
        flat = np.array(jax.device_get(x)).reshape(-1)
        flat[1::3] = -0.0
        return jax.device_put(flat.reshape(x.shape), x.sharding)
    return state._replace(opt_state=jax.tree.map(plant, state.opt_state),
                          ef_residual=plant(state.ef_residual))


@pytest.mark.parametrize("path", ["flat", "optax"])
@pytest.mark.parametrize("workers", [1, 2])
@pytest.mark.parametrize("where", ["gradient", "loss"])
def test_a_skipped_step_is_bit_identical_with_negative_zeros(where, workers,
                                                             path):
    """A skipped sparse step leaves every float leaf bit-identical, the
    sign of a -0.0 in the momentum and the residual included, whether the
    non-finite value is in the gradient or in the loss alone (finite
    gradient); the step counter and the integer optimizer leaves advance."""
    from gaussiank_sgd_tpu.parallel.flat_opt import FlatSGDM
    params, base_loss, make_batch = make_problem()

    def loss_fn(p, mstate, batch, rng):
        loss, out = base_loss(p, mstate, batch, rng)
        # a batch flagged by its first label poisons the loss alone
        poison = jnp.where(batch[1][0, 0] > 1e6, jnp.inf, 0.0)
        return loss + jax.lax.stop_gradient(poison), out

    mesh = data_parallel_mesh(workers)
    spec = get_compressor("gaussian_fused", density=0.05)
    plan = plan_for_params(params, 0.05)
    if path == "flat":
        ts = build_dp_train_step(
            loss_fn, None, spec, plan, mesh, wire="off",
            flat_opt=FlatSGDM(lr=0.05, momentum=0.9, weight_decay=0.01))
    else:
        ts = build_dp_train_step(
            loss_fn, optax.sgd(optax.constant_schedule(0.05), momentum=0.9),
            spec, plan, mesh, wire="off")
    state = ts.init_state(params, jax.random.PRNGKey(42))
    batch = shard_batch(mesh, make_batch(16))
    for _ in range(2):                   # a momentum and a residual exist
        state, _m = ts.sparse_step(state, batch)
    state = _with_negative_zeros(state, mesh)

    def bits(tree):
        return [np.asarray(jax.device_get(x)).view(np.uint8).copy()
                for x in jax.tree_util.tree_leaves(tree)
                if jnp.issubdtype(x.dtype, jnp.floating)]

    def counters(tree):
        return [int(x) for x in jax.tree_util.tree_leaves(tree)
                if jnp.issubdtype(x.dtype, jnp.integer)]

    frozen = (state.params, state.model_state, state.opt_state,
              state.ef_residual, state.carry, state.comp_state)
    before, counted, step0 = bits(frozen), counters(state.opt_state), \
        int(state.step)
    assert any((b.view(np.float32) == 0).any() for b in before)
    x, y = make_batch(16)
    if where == "gradient":
        bad = (x.at[0, 0].set(jnp.nan), y)
    else:
        bad = (x, y.at[0, 0].set(1e7))
    state, m = ts.sparse_step(state, shard_batch(mesh, bad))
    assert float(m.skipped) == 1.0 and float(m.nonfinite) >= 1
    after = bits((state.params, state.model_state, state.opt_state,
                  state.ef_residual, state.carry, state.comp_state))
    for a, b in zip(before, after):
        assert np.array_equal(a, b)
    assert int(state.step) == step0 + 1
    assert counters(state.opt_state) == [c + 1 for c in counted]
    assert (path == "optax") == bool(counted)
    state, m = ts.sparse_step(state, batch)         # and the next one commits
    assert float(m.skipped) == 0.0


@pytest.mark.parametrize("wd", [0.0, 0.01])
@pytest.mark.parametrize("program", ["sparse", "dense"])
def test_three_steps_of_the_flat_update_match_the_optax_chain(program, wd):
    """Three steps of either program through the commit equal
    ``test_flat_opt_matches_optax_trajectory``'s reference (the optax
    chain, f32 exchange) as before, with weight decay on and off: momentum
    and parameters both."""
    from gaussiank_sgd_tpu.parallel.flat_opt import FlatSGDM
    params, loss_fn, make_batch = make_problem()
    mesh = data_parallel_mesh()
    spec = get_compressor("topk", density=0.25)
    plan = plan_for_params(params, 0.25, None)
    chain = ([optax.add_decayed_weights(wd)] if wd else []) \
        + [optax.sgd(0.05, momentum=0.9)]
    ts_ref = build_dp_train_step(loss_fn, optax.chain(*chain), spec, plan,
                                 mesh, wire="off")
    ts_flat = build_dp_train_step(
        loss_fn, None, spec, plan, mesh, wire="off",
        flat_opt=FlatSGDM(lr=0.05, momentum=0.9, weight_decay=wd))
    s_ref = ts_ref.init_state(params, jax.random.PRNGKey(42))
    s_flat = ts_flat.init_state(params, jax.random.PRNGKey(42))
    batch = shard_batch(mesh, make_batch(64))
    for _ in range(3):
        s_ref, _m = getattr(ts_ref, program + "_step")(s_ref, batch)
        s_flat, _m = getattr(ts_flat, program + "_step")(s_flat, batch)
    np.testing.assert_allclose(
        np.asarray(ravel_pytree(s_flat.params)[0]),
        np.asarray(ravel_pytree(s_ref.params)[0]), rtol=1e-5, atol=1e-6)
    trace = [x for x in jax.tree_util.tree_leaves(s_ref.opt_state)
             if getattr(x, "ndim", 0)]
    np.testing.assert_allclose(
        np.asarray(s_flat.opt_state["m"]),
        np.concatenate([np.asarray(t).reshape(-1) for t in trace]),
        rtol=1e-5, atol=1e-6)
