"""Layer: model. Scope `attn_full` inside `fwd_bwd`: the full layers' attention (softmax(q k^T + mask) v and its backward pass, the recomputed forward included; not the projections).
Self time of the device operations whose `op_name` carries the scope, per
step of the profiled sparse block, averaged over the chips. None where the
program names no such scope. Moves `examples_per_s`. Source: device_trace."""

from benchmarks import model_scopes


def read(run):
    return model_scopes.scope_ms(run, "attn_full")
