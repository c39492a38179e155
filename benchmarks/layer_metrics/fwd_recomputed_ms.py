"""Layer: model. What `nn.remat` costs the step: under `fwd_bwd`, the operations
whose `op_name` holds `rematted_computation` (a layer's forward pass run again
inside the backward pass; `_SAVED` keeps the attention kernel's output only).
WITHOUT the kernels that carry no `op_name` (`ragged-dot-none`: a third of its
forward calls are recomputed ones), whose pass the trace does not say. Self
time per step of the profiled sparse block, the chips' mean. Moves
`examples_per_s`. Source: device_trace."""

from benchmarks import scope_tree


def read(run):
    return scope_tree.pass_ms(run, "recomputed")
