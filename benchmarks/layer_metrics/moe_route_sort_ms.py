"""Layer: model. Scope `moe_route_sort` inside `moe_router`
(`models/mellum2.route`): the stable `argsort` of the (token, expert)
assignments by held expert, the inverse permutation's scatter and the rows
each held expert got; what is left of `moe_router_ms` is the float32 product,
the scores and the top-k. Integers: it has no backward pass. Self time of the
device operations per step of the profiled sparse block, the chips' mean,
forward, recomputed and backward together (`scope_tree.py`). None where the
trace names no such scope. Moves `examples_per_s`. Source: device_trace."""

from benchmarks import scope_tree


def read(run):
    return scope_tree.ms(run, "moe_route_sort")
