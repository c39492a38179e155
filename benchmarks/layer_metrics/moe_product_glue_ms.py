"""Layer: model. Scope `moe_product_glue` inside `moe_experts`
(`models/mellum2.grouped_product` and its backward): what runs around the
`ragged-dot` kernels and is NOT the kernel (`ragged_dot_ms` reads that: the
compiler gives it no `op_name`): the weights' cast to bfloat16, the
transposition for the rows' cotangent, the zeroing of the rows past the last
group forward and backward, the cast of the weights' cotangent. Self time of
the device operations per step of the profiled sparse block, the chips' mean,
forward, recomputed and backward together (`scope_tree.py`). None where the
trace names no such scope. Moves `examples_per_s`. Source: device_trace."""

from benchmarks import scope_tree


def read(run):
    return scope_tree.ms(run, "moe_product_glue")
