"""The tests' throw-away benchmark root: two configurations (a tiny VGG and,
with its plain reference, a tiny token model), four traffic mixes (two of
them with one arm: their round is ["sparse"]), six cells and a per-layer
metric, written as new files and entries only.
Nothing that is under `benchmarks/` is edited or copied; the harness finds
each new file by the name in the new BENCHMARK.json. (No JAX in here:
`record_trace.py` imports it on the chip, `conftest.py` on the CPU.)"""

import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmarks")

TINY_STAGES = [16, "M", 32, "M", 64, "M", "M", "M"]

# Limits of the throw-away configuration, set the way the real ones are: from
# the sound runs' largest and the float8 control's smallest over six seeds at
# this size on the CPU (loss_gap 0.0020 against 0.0170, grad_rel_err 0.059
# against 0.157; the two norm gaps do not separate at batch 8 and only guard
# against a step that is not taken). The band on `selected_over_k` is wide: a
# 60 000-parameter net on 64 examples swings its selection count in a way
# the real configurations do not (0.8-1.2 on the chip).
TINY_LIMITS = {
    "loss_gap": 0.006, "grad_rel_err": 0.1, "grad_norm_gap": 0.5,
    "delta_norm_gap": 0.5, "double_counted": 0, "lost": 0, "pad_nonzero": 0,
    "state_mismatches": 0, "sent_step1_over_k": 1.0,
    "sent_mantissa": 1e-4, "residual_mantissa": 1e-4,
    "momentum_mantissa": 1e-4, "selected_over_k": [0.2, 200.0],
    "compiles_in_window": 0, "failed_steps": 0,
}


# The token model that the harness is put to at a tiny size (PR 27): the
# program's `lstm` on its synthetic `ptb`, the first configuration here whose
# batches are integers [rows, positions], whose head is a vocabulary
# projection and whose embedding has rows that a batch never names (their
# gradient is exactly zero, their residual too, and they are never sent).
# Float32 throughout and no dropout, so its limits are tight: over three
# seeds on one CPU device and on four the sound runs read at most 2.5e-7
# (losses) and 1.5e-7 (the gradient, its head leaf, the worst leaf's norm,
# the parameters' change), the float8 control at least 3.2e-5 (`loss_gap`),
# 0.039 (`grad_rel_err`), 0.053 (`head_grad_rel_err`), 0.0023 and 0.0052
# (the two norm gaps).
LM_ARCH = {"vocab": 300, "embed": 24, "hidden": 32, "layers": 2,
           "positions": 12}
LM_LIMITS = dict(TINY_LIMITS, loss_gap=1e-5, grad_rel_err=1e-3,
                 head_grad_rel_err=1e-3, grad_norm_gap=5e-4,
                 delta_norm_gap=5e-4)

LM_REFERENCE = '''"""Plain reference of the tests' token model: an embedding, stacked LSTM
layers (gates i|f|g|o; the input projection has a bias, the recurrent one
none; every window starts from a zero state), a linear vocabulary
projection, the mean cross-entropy of a token."""

import jax
import jax.numpy as jnp

from . import common as C


def _plan(cfg):
    a = cfg["arch"]
    plan = [("Embed_0/embedding", (a["vocab"], a["embed"]), 1.0)]
    for i in range(a["layers"]):
        d, h = (a["embed"] if i == 0 else a["hidden"]), a["hidden"]
        plan += [(f"lstm_{i}/wx/kernel", (d, 4 * h), d ** -0.5),
                 (f"lstm_{i}/wx/bias", (4 * h,), 0.0),
                 (f"lstm_{i}/wh", (h, 4 * h), h ** -0.5)]
    return plan + [("Dense_0/kernel", (a["hidden"], a["vocab"]),
                    a["hidden"] ** -0.5), ("Dense_0/bias", (a["vocab"],), 0.0)]


def init_params(key, cfg):
    return {p: (C.normal_init(jax.random.fold_in(key, i), shape, std) if std
                else jnp.zeros(shape, jnp.float32))
            for i, (p, shape, std) in enumerate(_plan(cfg))}


def param_shapes(cfg):
    return {p: shape for p, shape, _ in _plan(cfg)}


def loss(params, batch, cfg, precision="float32"):
    """batch = (tokens i32 [B, T], next tokens i32 [B, T], None)."""
    x, y, _ = batch
    h = params["Embed_0/embedding"][x]
    zero = jnp.zeros((x.shape[0], cfg["arch"]["hidden"]), jnp.float32)
    for i in range(cfg["arch"]["layers"]):
        xw = C.dense(h, params[f"lstm_{i}/wx/kernel"],
                     params[f"lstm_{i}/wx/bias"], precision)

        def step(carry, xw_t, wh=params[f"lstm_{i}/wh"]):
            c, out = carry
            g = jnp.split(xw_t + C.dense(out, wh, 0.0, precision), 4, -1)
            c = jax.nn.sigmoid(g[1]) * c + jax.nn.sigmoid(g[0]) * jnp.tanh(g[2])
            out = jax.nn.sigmoid(g[3]) * jnp.tanh(c)
            return (c, out), out

        h = jnp.swapaxes(jax.lax.scan(step, (zero, zero),
                                      jnp.swapaxes(xw, 0, 1))[1], 0, 1)
    logits = C.dense(h, params["Dense_0/kernel"], params["Dense_0/bias"])
    return C.cross_entropy(logits.reshape(-1, logits.shape[-1]),
                           y.reshape(-1))
'''


def lm_config() -> dict:
    """`tiny_lstm`: what a configuration's file gives the harness, for a
    model that is no image classifier."""
    a, batch, steps = LM_ARCH, 4, 6
    return {
        "name": "tiny_lstm", "reference": "tiny_lstm", "arch": a,
        "trainer": {
            "dnn": "lstm", "dataset": "ptb", "batch_size": batch, "lr": 0.5,
            "momentum": 0.9, "weight_decay": 0.0005, "epochs": 160,
            "lr_milestones": [0.5, 0.75], "lr_decay": 0.1,
            "warmup_epochs": 5.0, "density": 0.01,
            "compress_warmup_steps": 0, "compute_dtype": "float32",
            "overlap": "auto", "policy": "static", "max_steps": 1000000,
            "wire": "off", "carry_hidden": False,
            "model_kwargs": {"embed_dim": a["embed"],
                             "hidden_dim": a["hidden"],
                             "num_layers": a["layers"], "dropout": 0.0}},
        "sparse_compressor": "auto",
        # an epoch is `steps` windows of `positions` tokens a row
        "examples_per_worker": batch * steps,
        "dataset_kwargs": {"vocab_size": a["vocab"],
                           "bptt": a["positions"]},
        "dataset_kwargs_per_worker": {
            "synthetic_tokens_n": batch * (a["positions"] * steps + 1)},
        "states": {"compute_dtype": "float32", "grad_dtype": "float32",
                   "residual_dtype": "float32", "momentum_dtype": "float32",
                   "wire_format": "i32f32", "kernel_mode": "interpret",
                   "buckets": 1},
        "limits": LM_LIMITS, "head_leaf": "Dense_0/kernel"}


def write_tiny_root(root: str) -> None:
    """A whole benchmark root of throw-away entries: nothing that is under
    `benchmarks/` is edited or copied, the harness finds each new file by the
    name in the new BENCHMARK.json."""
    bdir = os.path.join(root, "benchmarks")
    for sub in ("configs", "traffic", "layer_metrics", "reference"):
        os.makedirs(os.path.join(bdir, sub), exist_ok=True)
    with open(os.path.join(bdir, "configs", "tiny_lstm.json"), "w") as f:
        json.dump(lm_config(), f)
    with open(os.path.join(bdir, "reference", "tiny_lstm.py"), "w") as f:
        f.write(LM_REFERENCE)
    with open(os.path.join(HERE, "configs", "vgg16_cifar10.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "tiny_vgg"
    cfg["trainer"].update(batch_size=8, lr=0.005, wire="off",
                          model_kwargs={"cfg": TINY_STAGES})
    cfg["examples_per_worker"] = 16
    cfg["arch"]["stages"] = TINY_STAGES
    cfg["states"]["kernel_mode"] = "interpret"
    cfg["matmul_layers"] = [{"name": "conv0", "positions": 1024, "k": 27,
                             "n": 16}]
    cfg["limits"] = TINY_LIMITS
    with open(os.path.join(bdir, "configs", "tiny_vgg.json"), "w") as f:
        json.dump(cfg, f)
    for name, workers, rnd in (
            ("quick1", 1, ["dense", "sparse", "sparse"]),
            ("quick4", 4, ["dense", "sparse", "sparse"]),
            ("solo1", 1, ["sparse"]), ("solo4", 4, ["sparse"])):
        mix = {"name": name, "nworkers": workers, "block_seconds": 0.2,
               "round": rnd, "log_every": 10}
        with open(os.path.join(bdir, "traffic", name + ".json"), "w") as f:
            json.dump(mix, f)
    with open(os.path.join(bdir, "layer_metrics", "sparse_steps.py"),
              "w") as f:
        f.write('"""Throw-away metric: sparse steps taken in the window."""'
                '\n\n\ndef read(run):\n'
                '    return float(run["totals"]["sparse"]["steps"])\n')
    bench = {
        "command": ["python3", "benchmarks/run.py"], "paths": ["benchmarks"],
        "run_seconds": 1,
        "configs": [{"name": "tiny_vgg", "source": "throw-away",
                     "file": "benchmarks/configs/tiny_vgg.json",
                     "reduced": [], "why": "test"},
                    {"name": "tiny_lstm", "source": "throw-away",
                     "file": "benchmarks/configs/tiny_lstm.json",
                     "reduced": [], "why": "test: a token model"}],
        "workloads": [
            {"name": "tiny_dp1", "config": "tiny_vgg", "traffic": "quick1",
             "chips": 1, "why": "test"},
            {"name": "tiny_dp4", "config": "tiny_vgg", "traffic": "quick4",
             "chips": 4, "why": "test"},
            {"name": "tiny_solo1", "config": "tiny_vgg", "traffic": "solo1",
             "chips": 1, "why": "test: one arm, no dense baseline"},
            {"name": "tiny_solo4", "config": "tiny_vgg", "traffic": "solo4",
             "chips": 4, "why": "test: one arm on four devices"},
            {"name": "tiny_lm_solo1", "config": "tiny_lstm",
             "traffic": "solo1", "chips": 1,
             "why": "test: a token model, one arm"},
            {"name": "tiny_lm_solo4", "config": "tiny_lstm",
             "traffic": "solo4", "chips": 4,
             "why": "test: a token model, one arm on four devices"}],
        "end_to_end": [
            {"name": "examples_per_s", "unit": "examples/s",
             "better": "higher", "bound": 0.1, "source": "host_clock"},
            {"name": "dense_examples_per_s", "unit": "examples/s",
             "better": "higher", "bound": 0.1, "source": "host_clock",
             "workloads": ["tiny_dp1", "tiny_dp4"]},
            {"name": "step_ms_p95", "unit": "ms", "better": "lower",
             "bound": 0.1, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
             "source": "host_clock"}],
        "per_layer": [
            {"name": "sparse_steps", "unit": "steps", "better": "higher",
             "source": "program_counter", "layer": "host loop",
             "moves": "examples_per_s"},
            {"name": "data_wait_ms", "unit": "ms", "better": "lower",
             "source": "host_clock", "layer": "host loop",
             "moves": "examples_per_s"},
            {"name": "ef_select_ms", "unit": "ms", "better": "lower",
             "source": "device_trace", "layer": "EF and select kernel",
             "moves": "examples_per_s"}],
    }
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
