"""Layer: model. Scope `moe_router` inside `fwd_bwd`: the router: its float32 product, softmax, top-k, and the sort of the assignments by held expert.
Self time of the device operations whose `op_name` carries the scope, per
step of the profiled sparse block, averaged over the chips. None where the
program names no such scope. Moves `examples_per_s`. Source: device_trace."""

from benchmarks import model_scopes


def read(run):
    return model_scopes.scope_ms(run, "moe_router")
