"""Layer: model. Scope `lm_head` inside `fwd_bwd`: the vocabulary projection over the rows held, forward and backward.
Self time of the device operations whose `op_name` carries the scope, per
step of the profiled sparse block, averaged over the chips. None where the
program names no such scope. Moves `examples_per_s`. Source: device_trace."""

from benchmarks import model_scopes


def read(run):
    return model_scopes.scope_ms(run, "lm_head")
