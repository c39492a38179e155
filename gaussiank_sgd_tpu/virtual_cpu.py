"""Virtual multi-device CPU platform provisioning — the ONE copy.

SURVEY.md §4 "Multi-node without a cluster": every distributed code path in
this framework is testable without hardware by forcing an n-device CPU
platform. Shared by tests/conftest.py, ``GKSGD_FORCE_VIRTUAL_CPU`` in
train.py, __graft_entry__.dryrun_multichip's children, and the analysis
scripts.

This module imports nothing at module scope (so it can be imported before
jax); ``provision(n)`` must be called before any jax operation initializes
the backend, though importing jax first is harmless.
"""

from __future__ import annotations

import os


def provision(n_devices: int) -> None:
    """Force an ``n_devices``-device CPU platform for this process.

    The env var is set too, so child processes inherit the CPU pin (a
    chip belongs to one process; a CPU-provisioned parent's children must
    never reach for it)."""
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", int(n_devices))
