"""Layer: model. Scope `attn_mla` inside `fwd_bwd`: latent attention proper
(softmax(q k^T + mask) v over query/key heads of 192 and value heads of 128,
and its backward pass; the Mosaic kernels `splash_mha_*` and what XLA runs
around them; not the projections, `mla_proj_ms`). Self time of the device
operations whose `op_name` carries the scope, per step of the profiled
sparse block, averaged over the chips. None where the program names no such
scope. Moves `examples_per_s`. Source: device_trace."""

from benchmarks import model_scopes


def read(run):
    return model_scopes.scope_ms(run, "attn_mla")
