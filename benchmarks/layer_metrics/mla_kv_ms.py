"""Layer: model. Scope `mla_kv` inside `mla_proj`
(`models/joyai_flash.LatentAttention`): `kv_a_proj`, `kv_a_norm` (its
`rms_norm` counts here) and `kv_b_proj`: keys and values down to the latent of
512 and up to the heads. The four `mla_*_ms` sum to `mla_proj_ms`. The scope
whole. Self time of the device operations per step of the profiled sparse
block, the chips' mean, forward, recomputed and backward together
(`scope_tree.py`). None where the trace names no such scope. Moves
`examples_per_s`. Source: device_trace."""

from benchmarks import scope_tree


def read(run):
    return scope_tree.under_ms(run, "mla_kv")
