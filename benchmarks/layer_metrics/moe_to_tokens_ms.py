"""Layer: model. Scope `moe_to_tokens` inside `moe_experts`
(`models/mellum2.to_tokens`): the pick of every assignment's row, the `live`
mask, the weighted sum over a token's experts, and backward the gathers of the
cotangent's rows and of each assignment's weight. Its recomputed forward is
dead code (only its arguments are kept). Self time of the device operations
per step of the profiled sparse block, the chips' mean, forward, recomputed
and backward together (`scope_tree.py`). None where the trace names no such
scope. Moves `examples_per_s`. Source: device_trace."""

from benchmarks import scope_tree


def read(run):
    return scope_tree.ms(run, "moe_to_tokens")
