"""Layer: model. Scope `mla_q` inside `mla_proj`
(`models/joyai_flash.LatentAttention`): `q_a_proj`, `q_a_norm` (its `rms_norm`
counts here) and `q_b_proj`: the queries down to rank 1536 and up to the
heads. The four `mla_*_ms` sum to `mla_proj_ms`. The scope whole. Self time of
the device operations per step of the profiled sparse block, the chips' mean,
forward, recomputed and backward together (`scope_tree.py`). None where the
trace names no such scope. Moves `examples_per_s`. Source: device_trace."""

from benchmarks import scope_tree


def read(run):
    return scope_tree.under_ms(run, "mla_q")
