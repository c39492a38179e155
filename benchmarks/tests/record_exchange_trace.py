#!/usr/bin/env python3
"""Record the small FOUR-CHIP traces that `test_exchange_metrics.py` reads
`exchange_ms` and each arm's collectives from (PR 26). Run on a machine
with four TPU chips:

    python3 benchmarks/tests/record_exchange_trace.py chiprun_out/testdata_dp4

It builds the tests' tiny throw-away four-worker cell (`tiny_dp4`) with the
program's tracing on, warms both trainers up, and profiles one block of two
steps of each with the benchmark's own profiler options (device events
only). It copies out each trace (as `.xspace.pb`: `test_trace_reduce.py`
reduces every `*.xplane.pb` under testdata/ as one trace) and the block's
host timings, and prints what `span_reduce.reduce_device` and
`trace_reduce.reduce_block` read from each, for the test to be held to.
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

NAME = "tiny_dp4_{arm}_2steps"
STEPS = 2


def main(out: str) -> int:
    import jax
    if jax.default_backend() != "tpu" or jax.device_count() < 4:
        print("record_exchange_trace.py: needs four TPU chips",
              file=sys.stderr)
        return 2
    from tiny_root import write_tiny_root
    from benchmarks import harness, span_reduce as sr, trace_reduce
    root = tempfile.mkdtemp(prefix="tiny_root_")
    write_tiny_root(root)
    cell = harness.load_cell("tiny_dp4", root=root)
    cell["config_data"]["states"]["kernel_mode"] = "mosaic"
    out_dir = harness.make_out_dir()
    os.makedirs(out, exist_ok=True)
    try:
        arms, _ = harness.build_arms(cell, 3, out_dir, True)
        for name, arm in arms.items():
            harness.warm_up(arm, cell["mix"])
            arm.steps_per_block = STEPS
            tdir = os.path.join(out_dir, "trace", f"{name}_0")
            block = harness.run_block(arm, tdir)
            path = trace_reduce.find_xplanes(tdir)[0]
            stem = os.path.join(out, NAME.format(arm=name))
            shutil.copy(path, stem + ".xspace.pb")
            with open(stem + ".block.json", "w") as f:
                json.dump(block, f)
            dev = sr.reduce_device(tdir, STEPS)
            old = trace_reduce.reduce_block(tdir, block, 10)
            print(json.dumps({
                "arm": name, "bytes": os.path.getsize(path),
                "chips": dev["chips"],
                "scope_ms_per_step": {k or "none": 1e3 * v for k, v in
                                      dev["scope_s_per_step"].items()},
                "collective_ms_per_step":
                    1e3 * old["collective_s_per_step"],
                "collectives": sorted(
                    (k, v) for k, v in old["by_name"].items()
                    if any(part.startswith(trace_reduce.COLLECTIVE_MARKS)
                           for part in k.split(" ")))}))
        harness.close_arms(arms)
    finally:
        harness.remove_out_dir(out_dir)
        shutil.rmtree(root, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1
                  else "chiprun_out/testdata_dp4"))
