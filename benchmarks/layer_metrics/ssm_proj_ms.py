"""Layer: model. Scopes `ssm_in_proj` and `ssm_out_proj` inside `ssm`: the mixer's
products (2688 -> 10 304 going in, 4096 -> 2688 coming out, a token),
compute-bound, with what XLA fuses into them. Self time of the device operations
per step of the profiled sparse block, the chips' mean, forward, recomputed and
backward together. None where the trace names no such scope. Moves
`examples_per_s`. Source: device_trace."""

from benchmarks import ssd_ops


def read(run):
    return ssd_ops.scopes_ms(run, ("ssm_in_proj", "ssm_out_proj"))
