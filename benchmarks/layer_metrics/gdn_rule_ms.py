"""Layer: model. Scope `gdn_rule` inside `linear_attn` (`delta.chunked_rule` and what
feeds it): the gates `beta` and `g`, the L2 norms of q and k, and the gated delta
rule in chunks of 64 tokens: the chunks' batched products and the inverse of their
unit lower-triangular systems, then a scan over the 128 chunks of a sequence with
the state of 128 x 128 a value head in float32; XLA's fusions and products, no
kernel. Self time of the device operations per step of the profiled sparse block,
the chips' mean, forward, recomputed and backward together. None where the trace
names no such scope. Moves `examples_per_s`. Source: device_trace."""

from benchmarks import gdn_ops


def read(run):
    return gdn_ops.scopes_ms(run, ("gdn_rule",))
