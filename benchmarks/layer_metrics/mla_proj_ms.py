"""Layer: model. Scope `mla_proj` inside `fwd_bwd`: latent attention's five
products (queries down to rank 1536 and up to 32 heads of 192, keys and
values down to rank 512 + 64 and up to 32 heads of 256, the output), the two
norms inside the bottlenecks, the rotary turn and the assembly of q and k,
forward, recomputed forward and backward. Self time of the device operations
whose `op_name` carries the scope, per step of the profiled sparse block,
averaged over the chips. None where the program names no such scope. Moves
`examples_per_s`. Source: device_trace."""

from benchmarks import model_scopes


def read(run):
    return model_scopes.scope_ms(run, "mla_proj")
