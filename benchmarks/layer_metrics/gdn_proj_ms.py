"""Layer: model. Scopes `gdn_in_proj` and `gdn_out_proj` inside `linear_attn`: the
mixer's products (2048 -> 12 288 and 2048 -> 64 going in, 4096 -> 2048 coming
out, a token), compute-bound, with what XLA fuses into them. Self time of the
device operations per step of the profiled sparse block, the chips' mean,
forward, recomputed and backward together. None where the trace names no such
scope. Moves `examples_per_s`. Source: device_trace."""

from benchmarks import gdn_ops


def read(run):
    return gdn_ops.scopes_ms(run, ("gdn_in_proj", "gdn_out_proj"))
