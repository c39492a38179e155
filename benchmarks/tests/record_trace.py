#!/usr/bin/env python3
"""Record the small device trace that `test_trace_reduce.py` checks the
reduction against. Run on a machine with a TPU:

    python3 benchmarks/tests/record_trace.py chiprun_out/testdata

It builds the tests' tiny throw-away cell (tiny_root.write_tiny_root), takes
the sparse trainer through its warm-up and traces one block of four steps
with the benchmark's own profiler options (device events only), then copies
the `.xplane.pb` and the block's host timings out. The file kept under
`benchmarks/testdata/` is this script's output on one TPU v5 lite chip.
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


def main(out: str) -> int:
    import jax
    if jax.default_backend() != "tpu":
        print("record_trace.py: needs a TPU", file=sys.stderr)
        return 2
    from tiny_root import write_tiny_root
    from benchmarks import harness, trace_reduce
    root = tempfile.mkdtemp(prefix="tiny_root_")
    write_tiny_root(root)
    cell = harness.load_cell("tiny_dp1", root=root)
    cell["config_data"]["states"]["kernel_mode"] = "mosaic"
    out_dir = harness.make_out_dir()
    os.makedirs(out, exist_ok=True)
    try:
        arms, _ = harness.build_arms(cell, 3, out_dir, False)
        arm = arms["sparse"]
        harness.warm_up(arm, cell["mix"])
        arm.steps_per_block = 4
        tdir = os.path.join(out_dir, "trace")
        block = harness.run_block(arm, tdir)
        path = trace_reduce.find_xplanes(tdir)[0]
        shutil.copy(path, os.path.join(out, "tiny_sparse_4steps.xplane.pb"))
        with open(os.path.join(out, "tiny_sparse_4steps.block.json"),
                  "w") as f:
            json.dump(block, f)
        red = trace_reduce.reduce_block(tdir, block, 10)
        red.pop("by_name")
        print(json.dumps(red))
        print("trace bytes", os.path.getsize(path))
        harness.close_arms(arms)
    finally:
        harness.remove_out_dir(out_dir)
        shutil.rmtree(root, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else "chiprun_out/testdata"))
