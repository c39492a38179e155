"""Layer: model. The `ssm_norm_gate` passes' share of the memory roofline: the least
time they could take (`ssd_ops.py`: 6 bytes an entry of the scan's `[tokens, 4096]`
output forward, reading output and gate and writing the normed product in bfloat16,
the same again where the block is recomputed, 10 backward, reading the cotangent,
output and gate and writing two cotangents; over the chip's HBM bandwidth) over
`ssm_norm_gate_ms`. Cannot pass 100%. None where the trace names no such scope or
the configuration no `arch.ssm_layers`. Moves `examples_per_s`.
Source: device_trace."""

from benchmarks import ssd_ops


def read(run):
    return ssd_ops.norm_gate_roofline_share(run)
