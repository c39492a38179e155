"""Layer: model. Scopes `conv_in_proj` and `conv_out_proj` inside `short_conv`: the
mixer's two products (2048 -> 6144 and 2048 -> 2048 a token), compute-bound,
with what XLA fuses into them (the `operator_norm`'s scaling rides in the first
one's fusion on the chip). With `short_conv_gate_ms` it is `short_conv_ms`, but
for what lies directly under `short_conv`. Self time of the device operations
per step of the profiled sparse block, the chips' mean, forward, recomputed and
backward together. None where the trace names no such scope. Moves
`examples_per_s`. Source: device_trace."""

from benchmarks import conv_ops


def read(run):
    return conv_ops.scopes_ms(run, ("conv_in_proj", "conv_out_proj"))
