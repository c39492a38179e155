"""Layer: model. Scope `ssm_conv` inside `ssm` (`delta.conv_silu` with a bias): the
depthwise causal convolution of 4 taps with SiLU over the 6144 channels of
`x B C`, shifted multiply-adds that XLA fuses and no kernel, held apart from the
products beside it by `optimization_barrier`s. Self time of the device operations
per step of the profiled sparse block, the chips' mean, forward, recomputed and
backward together. None where the trace names no such scope. Moves
`examples_per_s`. Source: device_trace."""

from benchmarks import ssd_ops


def read(run):
    return ssd_ops.scopes_ms(run, ("ssm_conv",))
