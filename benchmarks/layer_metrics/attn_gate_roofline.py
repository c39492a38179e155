"""Layer: model. The `attn_gate` passes' share of the memory roofline: the least time
they could take (`gate_ops.py`: 6 bytes an entry of the attention's `[tokens, 4096]`
output forward, reading output and gate and writing their product in bfloat16, the
same again where the layer is recomputed, 10 backward, reading the cotangent, output
and gate and writing two cotangents; over the chip's HBM bandwidth) over
`attn_gate_ms`. Cannot pass 100%: only what the passes MUST move is counted, not the
counter's mean nor any temporary XLA writes between its fusions. None where the
trace names no such scope or the configuration no `arch.gated_attention_layers`.
Moves `examples_per_s`. Source: device_trace."""

from benchmarks import gate_ops


def read(run):
    return gate_ops.gate_roofline_share(run)
