"""Layer: model. Scope `gdn_conv` inside `linear_attn` (`delta.conv_silu`): the
depthwise causal convolution of 4 taps with SiLU over the 8192 channels of
`[q | k | v]`, shifted multiply-adds that XLA fuses and no kernel, held apart from
the products beside it by `optimization_barrier`s. Self time of the device
operations per step of the profiled sparse block, the chips' mean, forward,
recomputed and backward together. None where the trace names no such scope.
Moves `examples_per_s`. Source: device_trace."""

from benchmarks import gdn_ops


def read(run):
    return gdn_ops.scopes_ms(run, ("gdn_conv",))
