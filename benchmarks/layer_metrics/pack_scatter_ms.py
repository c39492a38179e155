"""Layer: sparse step program. Scopes `pack` and `scatter` together: the gather
of the k sent pairs, their zeroing in the residual, the decode and the
scatter-add into the momentum. Self time of the device operations whose
`op_name` carries the scope, per step of the profiled sparse block, averaged
over the chips. Moves `examples_per_s`. Source: device_trace."""

from benchmarks import span_reduce


def read(run):
    return span_reduce.scope_ms(run, "pack", "scatter")
