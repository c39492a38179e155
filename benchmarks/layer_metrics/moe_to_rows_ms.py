"""Layer: model. Scope `moe_to_rows` inside `moe_experts`
(`models/mellum2.to_rows`): the gather of each sorted assignment's token row,
`x[first // top]`, and its cotangent's way back to tokens (a gather and a sum
over a token's assignments, not a scatter). Self time of the device operations
per step of the profiled sparse block, the chips' mean, forward, recomputed
and backward together (`scope_tree.py`). None where the trace names no such
scope. Moves `examples_per_s`. Source: device_trace."""

from benchmarks import scope_tree


def read(run):
    return scope_tree.ms(run, "moe_to_rows")
