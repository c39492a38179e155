"""Layer: model. Scope `mla_out` inside `mla_proj`
(`models/joyai_flash.LatentAttention`): `o_proj`, the output projection over
the heads' values. The four `mla_*_ms` sum to `mla_proj_ms`. The scope whole.
Self time of the device operations per step of the profiled sparse block, the
chips' mean, forward, recomputed and backward together (`scope_tree.py`). None
where the trace names no such scope. Moves `examples_per_s`. Source:
device_trace."""

from benchmarks import scope_tree


def read(run):
    return scope_tree.under_ms(run, "mla_out")
