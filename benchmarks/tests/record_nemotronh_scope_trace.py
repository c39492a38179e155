#!/usr/bin/env python3
"""Record the small trace that `test_nemotronh_cell.py` checks the
state-space mixer's readers against (PR 48): the tiny `nemotron_h`
configuration of that file, by `record_scope_trace.py`'s own `record`. Run
on a machine with a TPU:

    python3 benchmarks/tests/record_nemotronh_scope_trace.py chiprun_out/testdata_scopes

and copy `tiny_nemotronh_scopes_4steps.*` to `benchmarks/testdata/`.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from record_scope_trace import record  # noqa: E402


def main(out: str) -> int:
    import jax
    if jax.default_backend() != "tpu":
        print("record_nemotronh_scope_trace.py: needs a TPU",
              file=sys.stderr)
        return 2
    import test_nemotronh_cell
    os.makedirs(out, exist_ok=True)
    print(json.dumps(record(test_nemotronh_cell.tiny_config(),
                            test_nemotronh_cell.CELL, out, "mosaic"),
                     indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1
                  else "chiprun_out/testdata_scopes"))
