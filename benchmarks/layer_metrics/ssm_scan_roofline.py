"""Layer: model. The state-space scan's share of its roofline: the least time the
RECURRENCE could take a step (`ssd_ops.py`: two products of 128 x 64 a token and
head forward, the write `dt B x^T` and the read `S^T C`, twice that backward, the
forward again where the block is recomputed, over the chip's bf16 peak; or its
bytes, x, B, C read and y written in bfloat16, dt in float32, and their cotangents
backward, over HBM bandwidth; the larger, which is the bytes) over `ssm_scan_ms`.
Counted from shapes, the same whatever implements the scan: the chunked form's
extra products and the state's trips through memory are not counted, so it cannot
pass 100%. None where the trace names no such scope or the configuration no
`arch.ssm_layers`. Moves `examples_per_s`. Source: device_trace."""

from benchmarks import ssd_ops


def read(run):
    return ssd_ops.scan_roofline_share(run)
