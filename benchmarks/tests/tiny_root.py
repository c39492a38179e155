"""The tests' throw-away benchmark root: a configuration, four traffic mixes
(two of them with one arm: their round is ["sparse"]), four cells and a
per-layer metric, written as new files and entries only.
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


def write_tiny_root(root: str) -> None:
    """A whole benchmark root of throw-away entries: nothing that is under
    `benchmarks/` is edited or copied, the harness finds each new file by the
    name in the new BENCHMARK.json."""
    bdir = os.path.join(root, "benchmarks")
    for sub in ("configs", "traffic", "layer_metrics"):
        os.makedirs(os.path.join(bdir, sub), exist_ok=True)
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
                     "reduced": [], "why": "test"}],
        "workloads": [
            {"name": "tiny_dp1", "config": "tiny_vgg", "traffic": "quick1",
             "chips": 1, "why": "test"},
            {"name": "tiny_dp4", "config": "tiny_vgg", "traffic": "quick4",
             "chips": 4, "why": "test"},
            {"name": "tiny_solo1", "config": "tiny_vgg", "traffic": "solo1",
             "chips": 1, "why": "test: one arm, no dense baseline"},
            {"name": "tiny_solo4", "config": "tiny_vgg", "traffic": "solo4",
             "chips": 4, "why": "test: one arm on four devices"}],
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
