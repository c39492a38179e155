"""Layer: model. Scope `dense_mlp` inside `fwd_bwd`: the gated MLP of the
leading layer that has no experts (three dense products of width 7168),
forward, recomputed forward and backward. Self time of the device operations
whose `op_name` carries the scope, per step of the profiled sparse block,
averaged over the chips. None where the program names no such scope. Moves
`examples_per_s`. Source: device_trace."""

from benchmarks import model_scopes


def read(run):
    return model_scopes.scope_ms(run, "dense_mlp")
