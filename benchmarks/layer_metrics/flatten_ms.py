"""Layer: sparse step program. Scope `flatten`: the flat gradient (concatenate,
cast, pad, clip). Self time of the device operations whose `op_name` carries
the scope, per step of the profiled sparse block, the chips' mean. Moves
`examples_per_s`. Source: device_trace."""

from benchmarks import span_reduce


def read(run):
    return span_reduce.scope_ms(run, "flatten")
