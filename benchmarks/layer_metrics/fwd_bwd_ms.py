"""Layer: sparse step program. Scope `fwd_bwd`: the forward and backward passes.
Self time of the device operations whose `op_name` carries the scope, per step
of the profiled sparse block, averaged over the chips. Moves `examples_per_s`.
Source: device_trace."""

from benchmarks import span_reduce


def read(run):
    return span_reduce.scope_ms(run, "fwd_bwd")
