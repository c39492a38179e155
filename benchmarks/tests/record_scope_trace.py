#!/usr/bin/env python3
"""Record the two small traces that `test_scope_tree.py` checks
`scope_tree.py` against (PR 36): the tiny `mellum2` and `joyai_flash`
configurations of `test_mellum2_cell.py` and `test_joyai_cell.py`, whose
sparse steps carry every scope a model opens. Run on a machine with a TPU:

    python3 benchmarks/tests/record_scope_trace.py chiprun_out/testdata_scopes

As `record_span_trace.py` does for the tiny VGG: each sparse trainer is
built by the harness, taken through its warm-up and one block of four steps
under the benchmark's own profiler options, and the trace (as `.xspace.pb`:
`test_trace_reduce.py` reduces every `.xplane.pb` under testdata/ as one
trace) and the block's host timings are copied out. Attention runs without
its kernels (32 positions are no tile of theirs); the grouped products are
the TPU compiler's own. It prints what `scope_tree` reads from each.
"""

import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

STEPS = 4


def write_root(root: str, config: dict, real_cell: str) -> str:
    """A throw-away benchmark root with one cell of `config` under the
    real cell's mix; the cell's name."""
    from benchmarks import harness
    bdir = os.path.join(root, "benchmarks")
    for sub in ("configs", "traffic"):
        os.makedirs(os.path.join(bdir, sub))
    with open(os.path.join(bdir, "configs", config["name"] + ".json"),
              "w") as f:
        json.dump(config, f)
    mix = harness.load_cell(real_cell)["mix"]
    with open(os.path.join(bdir, "traffic", mix["name"] + ".json"),
              "w") as f:
        json.dump(mix, f)
    real = harness.load_benchmark()
    cell = config["name"] + "_dp1"
    bench = {
        "command": real["command"], "paths": ["benchmarks"], "run_seconds": 1,
        "configs": [{"name": config["name"], "source": "throw-away",
                     "file": f"benchmarks/configs/{config['name']}.json",
                     "reduced": [], "why": "a recording"}],
        "workloads": [{"name": cell, "config": config["name"],
                       "traffic": mix["name"], "chips": 1,
                       "why": "a recording"}],
        "end_to_end": [m for m in real["end_to_end"]
                       if "workloads" not in m],
        "per_layer": []}
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return cell


def record(config: dict, real_cell: str, out: str, kernel_mode: str) -> dict:
    """One recording, named `<config's name>_scopes_4steps`; what
    `scope_tree` reads from it."""
    from benchmarks import harness, scope_tree, trace_reduce
    config["trainer"]["model_kwargs"]["kernels"] = False
    config["states"]["kernel_mode"] = kernel_mode
    name = f"{config['name']}_scopes_{STEPS}steps"
    root = tempfile.mkdtemp(prefix="scope_root_")
    out_dir = harness.make_out_dir()
    try:
        cell = harness.load_cell(write_root(root, config, real_cell),
                                 root=root)
        arms, _ = harness.build_arms(cell, 3, out_dir, True)
        arm = arms["sparse"]
        harness.warm_up(arm, cell["mix"])
        arm.steps_per_block = STEPS
        tdir = os.path.join(out_dir, "trace", "sparse_0")
        block = harness.run_block(arm, tdir)
        paths = trace_reduce.find_xplanes(tdir)
        shutil.copy(paths[0], os.path.join(out, name + ".xspace.pb"))
        with open(os.path.join(out, name + ".block.json"), "w") as f:
            json.dump(block, f)
        read = scope_tree.reduce_device(paths, block["steps"])
        harness.close_arms(arms)
    finally:
        harness.remove_out_dir(out_dir)
        shutil.rmtree(root, ignore_errors=True)
    size = os.path.getsize(os.path.join(out, name + ".xspace.pb"))
    if read is None:        # no device plane: not a TPU's trace
        return {"name": name, "bytes": size}
    return {"name": name, "bytes": size,
            "tree_ms": scope_tree.tree_line(read["rows"]),
            "passes_ms": {p: 1e3 * scope_tree.total(
                read["rows"], ("fwd_bwd",), which=p)
                for p in scope_tree.PASSES},
            "pathless_kernels_ms": {k: 1e3 * v for k, v in
                                    read["pathless_kernels"].items()},
            "modules": len(read["modules"]), "decode_s": read["decode_s"]}


def main(out: str) -> int:
    import jax
    if jax.default_backend() != "tpu":
        print("record_scope_trace.py: needs a TPU", file=sys.stderr)
        return 2
    import test_joyai_cell
    import test_mellum2_cell
    os.makedirs(out, exist_ok=True)
    for module in (test_mellum2_cell, test_joyai_cell):
        print(json.dumps(record(module.tiny_config(), module.CELL, out,
                                "mosaic"), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1
                  else "chiprun_out/testdata_scopes"))
