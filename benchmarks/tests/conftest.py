"""Tests of the benchmark's own code, on the CPU: `pytest benchmarks/tests -q`.

Not part of tier-1 (`pytest tests/`). The harness is driven by calling its
functions (the command itself refuses a CPU) on four virtual CPU devices, at
a tiny size, from a throw-away benchmark root that these tests write: a
configuration, a traffic mix, two cells and a per-layer metric added as files
and entries only, without editing a file that is there.
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from gaussiank_sgd_tpu import compile_cache, virtual_cpu  # noqa: E402

virtual_cpu.provision(4)
compile_cache.enable_compile_cache()

import pytest  # noqa: E402
from tiny_root import write_tiny_root  # noqa: E402


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny_benchmark"))
    write_tiny_root(root)
    return root
