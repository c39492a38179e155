"""Layer: EF and select kernel. Scope `ef_select`: the Mosaic kernel AND the
threshold controller around it, by the scope and not by the step's
`tpu_custom_call`s (`ef_select_ms` adds up every one of those and so cannot
list a cell whose model brings kernels of its own). Self time of the device
operations whose `op_name` carries the scope, per step of the profiled sparse
block, the chips' mean. Moves `examples_per_s`. Source: device_trace."""

from benchmarks import span_reduce


def read(run):
    return span_reduce.scope_ms(run, "ef_select")
