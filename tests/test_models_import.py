"""What importing the model zoo costs a run that uses none of its kernels
(PR 47): `models/__init__.py` imports every model file for every `--dnn`, so
a VGG-16 run on four chips imports `models/qwen3_next.py`, `blocks/delta.py`
and with it `ops/delta_rule.py` and `ops/delta_prologue.py`. Their import is
constants, `def`s and `defvjp`: no backend starts, nothing is traced or
compiled, and the four-worker VGG-16 sparse step builds and runs beside them.
In a subprocess, since whether a backend has started is the process's."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CODE = r"""
import sys
sys.path.insert(0, %(repo)r)
from gaussiank_sgd_tpu import virtual_cpu
virtual_cpu.provision(4)

import jax
from jax._src import xla_bridge

timed = []
jax.monitoring.register_event_duration_secs_listener(
    lambda name, seconds, **_: timed.append(name))

from gaussiank_sgd_tpu import models
from gaussiank_sgd_tpu.models.blocks import delta
from gaussiank_sgd_tpu.ops import delta_prologue, delta_rule

assert delta.delta_prologue is delta_prologue
assert delta.delta_rule is delta_rule
assert not xla_bridge.backends_are_initialized(), "the import started a backend"
assert not [n for n in timed if "/compile/" in n], timed
print("IMPORT_OK")

import jax.numpy as jnp
import numpy as np

from gaussiank_sgd_tpu.compressors import get_compressor
from gaussiank_sgd_tpu.parallel.bucketing import plan_for_params
from gaussiank_sgd_tpu.parallel.flat_opt import FlatSGDM
from gaussiank_sgd_tpu.parallel.mesh import data_parallel_mesh, shard_batch
from gaussiank_sgd_tpu.parallel.trainstep import build_dp_train_step
from gaussiank_sgd_tpu.training.losses import make_loss_fn

workers, each = 4, 2
spec = models.get_model("vgg16", "cifar10", dtype=jnp.bfloat16)
x = jax.random.normal(jax.random.PRNGKey(1),
                      (workers * each,) + spec.input_shape)
y = jnp.arange(workers * each, dtype=jnp.int32) %% spec.num_classes
variables = spec.module.init({"params": jax.random.PRNGKey(0)}, x[:2],
                             train=False)
params = variables["params"]
mstate = {k: v for k, v in variables.items() if k != "params"}
mesh = data_parallel_mesh(workers)
plan = plan_for_params(params, 0.001)
ts = build_dp_train_step(
    make_loss_fn(spec, recurrent=False), None,
    get_compressor("auto", density=0.001), plan, mesh,
    flat_opt=FlatSGDM(lr=0.1, momentum=0.9, weight_decay=5e-4))
state = ts.init_state(params, jax.random.PRNGKey(2), model_state=mstate)
state, m = ts.sparse_step(state, shard_batch(mesh, (x, y)))
assert np.isfinite(float(m.loss)) and float(m.skipped) == 0.0, m
assert 0 < float(m.num_selected) < plan.total_numel / 10, m.num_selected
print("VGG16_DP4_STEP_OK", plan.total_numel, float(m.loss),
      float(m.num_selected))
"""


def test_importing_the_models_starts_nothing_and_vgg16_steps_on_four_workers():
    env = dict(os.environ)
    env.pop("GKSGD_FORCE_VIRTUAL_CPU", None)
    proc = subprocess.run(
        [sys.executable, "-c", _CODE % {"repo": REPO}], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "IMPORT_OK" in proc.stdout, proc.stdout
    assert "VGG16_DP4_STEP_OK 14986698" in proc.stdout, proc.stdout
