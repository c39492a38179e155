"""Layer: model. The gated delta rule's share of its roofline: the least time the
RECURRENCE could take a step (`gdn_ops.py`: three products of 128 x 128 a token
and value head forward, `S k`, the rank-one write and `S q`, twice that backward,
the forward again where the layer is recomputed, over the chip's bf16 peak; or
its bytes, q, k, v read and o written in bfloat16, g and beta in float32, and
their cotangents backward, over HBM bandwidth; the larger, which is the bytes)
over `gdn_rule_ms`. Counted from shapes, the same whatever implements the rule:
the chunked form's extra products and the state's trips through memory are not
counted, so it cannot pass 100%. None where the trace names no such scope or the
configuration no `arch.linear_layers`. Moves `examples_per_s`. Source: device_trace."""

from benchmarks import gdn_ops


def read(run):
    return gdn_ops.rule_roofline_share(run)
