"""Layer: model. Scope `attn_gate` inside `attn_proj` (`models/mellum2.gated_output`):
the pass between the attention kernel and `o_proj`, `y = o * sigmoid(g)` with `g`
a fifth projection of the layer's input, entry by entry over `[tokens, 4096]` and
memory-bound, XLA's fusions and no kernel, held apart from its neighbours by
`optimization_barrier`s. Self time of the device operations per step of the
profiled sparse block, the chips' mean, forward, recomputed and backward together.
`attn_proj_ms` holds it too (the scope lies inside that one). None where the
trace names no such scope. Moves `examples_per_s`. Source: device_trace."""

from benchmarks import gate_ops


def read(run):
    return gate_ops.gate_ms(run)
