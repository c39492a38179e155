"""Layer: model. The experts, whole: the scope `moe_experts` inside `fwd_bwd`
(the gather of every assignment's row, the gates, the weighted sum back to
tokens, the masks, and their backward pass) AND the three grouped products
over the experts held, which XLA's `ragged-dot` kernels run with no
`op_name` (the configuration's `kernels_without_scope` gives them to this
scope; `ragged_dot_ms` reads them alone). Self time of those device
operations per step of the profiled sparse block, averaged over the chips.
None where the program names no such scope. Moves `examples_per_s`.
Source: device_trace."""

from benchmarks import model_scopes


def read(run):
    return model_scopes.scope_ms(run, "moe_experts")
