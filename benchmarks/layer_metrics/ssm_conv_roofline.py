"""Layer: model. The `ssm_conv` passes' share of the memory roofline: the least time
they could take (`ssd_ops.py`: 4 bytes a token and channel forward, reading x and
writing y in bfloat16, the same again where the block is recomputed, 6 backward,
reading dy and x and writing dx; over the chip's HBM bandwidth) over `ssm_conv_ms`.
Cannot pass 100%: only what the passes MUST move is counted, not the taps' and the
bias' own gradients nor any temporary XLA writes between its fusions. None where
the trace names no such scope or the configuration no `arch.ssm_layers`. Moves
`examples_per_s`. Source: device_trace."""

from benchmarks import ssd_ops


def read(run):
    return ssd_ops.conv_roofline_share(run)
