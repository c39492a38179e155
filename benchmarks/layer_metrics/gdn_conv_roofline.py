"""Layer: model. The `gdn_conv` passes' share of the memory roofline: the least time
they could take (`gdn_ops.py`: 4 bytes a token and channel forward, reading x and
writing y in bfloat16, the same again where the layer is recomputed, 6 backward,
reading dy and x and writing dx; over the chip's HBM bandwidth) over
`gdn_conv_ms`. Cannot pass 100%: only what the passes MUST move is counted, not
the taps' own gradient nor any temporary XLA writes between its fusions. None
where the trace names no such scope or the configuration no `arch.linear_layers`.
Moves `examples_per_s`. Source: device_trace."""

from benchmarks import gdn_ops


def read(run):
    return gdn_ops.conv_roofline_share(run)
