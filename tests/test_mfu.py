"""MFU accounting (VERDICT r2 item 2): the FLOPs numerator comes from XLA's
HLO cost analysis of the compiled program — exact for the conv/matmul terms
that dominate — and the peak table maps jax device_kind to public bf16
specs. On CPU there is no peak, so MFU is None (never a made-up number);
on a TPU the table does not know, the lookup raises."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gaussiank_sgd_tpu.telemetry import throughput
from gaussiank_sgd_tpu.telemetry.throughput import (device_peak_flops, mfu,
                                                    program_flops)


def test_program_flops_matches_matmul_analytic():
    m, k, n = 256, 128, 64

    @jax.jit
    def f(a, b):
        return a @ b

    a = jnp.zeros((m, k), jnp.float32)
    b = jnp.zeros((k, n), jnp.float32)
    flops = program_flops(f, a, b)
    assert flops is not None
    analytic = 2 * m * k * n
    assert 0.5 * analytic <= flops <= 2.0 * analytic, (flops, analytic)


def test_program_flops_scales_with_batch():
    @jax.jit
    def f(a, b):
        return jnp.sum(jnp.tanh(a @ b))

    k = 64
    small = program_flops(f, jnp.zeros((32, k)), jnp.zeros((k, k)))
    big = program_flops(f, jnp.zeros((256, k)), jnp.zeros((k, k)))
    assert small and big
    assert 4.0 <= big / small <= 16.0     # 8x batch -> ~8x flops


def test_mfu_none_paths():
    assert mfu(None, 0.01, 1e12) is None
    assert mfu(1e9, 0.01, None) is None
    assert mfu(1e9, 0.0, 1e12) is None
    got = mfu(1e12, 0.01, 197e12)
    np.testing.assert_allclose(got, 1e12 / (0.01 * 197e12))


def test_device_peak_flops_cpu_is_none():
    # the test suite runs on the virtual CPU platform (conftest.py)
    assert device_peak_flops(jax.devices()[0]) is None


def test_peak_table_is_exact_and_unknown_tpu_raises():
    """Exact device_kind keys: v5e is 197e12, and a TPU kind the table
    does not hold is an error — never a neighbouring generation's peak."""
    class FakeTpu:
        platform = "tpu"

        def __init__(self, kind):
            self.device_kind = kind

    assert device_peak_flops(FakeTpu("TPU v5 lite")) == 197e12
    assert device_peak_flops(FakeTpu("TPU v5p")) == 459e12
    for kind in ("TPU v5 litepod", "TPU v9", ""):
        with pytest.raises(KeyError, match="no peak FLOP/s"):
            device_peak_flops(FakeTpu(kind))


# ------------------------------------------------ the trainer's MFU probe
# It only runs where a peak is known, i.e. never on the CPU this suite runs
# on — so these fake the peak to enter it at all.

def _mfu_trainer(tmp_path, **kw):
    from gaussiank_sgd_tpu.training.config import TrainConfig
    from gaussiank_sgd_tpu.training.trainer import Trainer

    base = dict(dnn="mnistnet", dataset="mnist", batch_size=8, nworkers=1,
                lr=0.005, compressor="gaussian", density=0.01,
                compress_warmup_steps=2, max_steps=4, log_every=2,
                compute_dtype="float32", output_dir=str(tmp_path),
                eval_every_epochs=0, save_every_epochs=0)
    base.update(kw)
    return Trainer(TrainConfig(**base))


def test_trainer_mfu_probe_reuses_the_compiled_step(tmp_path, monkeypatch):
    """The probe lowers and compiles the step a second time; that must be
    a cache hit on the program that just ran, never a compile of its own
    (at the dense->sparse boundary it used to pick the step that had not
    run yet and paid its whole compile inside the log call)."""
    monkeypatch.setattr(throughput, "device_peak_flops",
                        lambda device: 1e12)
    in_probe, compiled_in_probe = [False], []

    def on_duration(event, duration, **kw):
        if in_probe[0] and event.endswith("backend_compile_duration"):
            compiled_in_probe.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    t = _mfu_trainer(tmp_path)
    real_probe = t._maybe_probe_mfu

    def probe(fn):
        in_probe[0] = True
        try:
            real_probe(fn)
        finally:
            in_probe[0] = False

    t._maybe_probe_mfu = probe
    t.fit()
    t.close()
    assert t._flops_per_step and t._peak_flops == 1e12
    assert compiled_in_probe == []
    recs = [json.loads(line) for line in
            open(os.path.join(t.run_dir, "metrics.jsonl"))]
    assert all("mfu" in r for r in recs if r["event"] == "train")


def test_trainer_mfu_probe_failure_is_an_error_where_a_peak_is_known(
        tmp_path, monkeypatch):
    def boom(jitted, *args):
        raise RuntimeError("cost analysis unavailable")

    monkeypatch.setattr(throughput, "device_peak_flops",
                        lambda device: 1e12)
    monkeypatch.setattr(throughput, "program_flops", boom)
    t = _mfu_trainer(tmp_path)
    with pytest.raises(RuntimeError, match="cost analysis unavailable"):
        t.fit()
    t.close()
