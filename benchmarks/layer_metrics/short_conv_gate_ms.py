"""Layer: model. Scope `conv_gate` inside `short_conv`: the pass between the mixer's
two products, `y = C * conv3(B * x)`: both gates and the three taps, elementwise
over `[tokens, 2048]` and memory-bound, XLA's fusions and no kernel, held apart
from the products by an `optimization_barrier`. Self time of the device
operations per step of the profiled sparse block, the chips' mean, forward,
recomputed and backward together. None where the trace names no such scope.
Moves `examples_per_s`. Source: device_trace."""

from benchmarks import conv_ops


def read(run):
    return conv_ops.scopes_ms(run, ("conv_gate",))
