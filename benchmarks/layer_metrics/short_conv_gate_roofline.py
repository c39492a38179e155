"""Layer: model. The `conv_gate` passes' share of the memory roofline: the least time
they could take (`conv_ops.py`: 8 bytes a token and channel forward, reading B,
C, x and writing y in bfloat16, the same again where the layer is recomputed, 14
backward, reading dy, B, C, x and writing the three cotangents; over the chip's
HBM bandwidth: at 7 operations to 8 bytes the pass is bound by memory) over
`short_conv_gate_ms`. Cannot pass 100%: only what the passes MUST move is
counted, not the taps' own gradient nor any temporary XLA writes between its
fusions. None where the trace names no such scope or the configuration no
`arch.conv_layers`. Moves `examples_per_s`. Source: device_trace."""

from benchmarks import conv_ops


def read(run):
    return conv_ops.gate_roofline_share(run)
