"""Layer: model. Scope `ssm_scan` inside `ssm` (`ssm.chunked_scan` and what feeds
it): `dt = softplus(dt + dt_bias)`, then the state-space recurrence in chunks of
128 tokens, a group of 8 heads at a time: a chunk's `C B^T` under its block of
decays, what the chunk writes, a scan over the 64 chunks of a sequence with the
state of 128 x 64 a head in float32, and the `D x` term; XLA's fusions and
products, no kernel. Self time of the device operations per step of the profiled
sparse block, the chips' mean, forward, recomputed and backward together. None
where the trace names no such scope. Moves `examples_per_s`.
Source: device_trace."""

from benchmarks import ssd_ops


def read(run):
    return ssd_ops.scopes_ms(run, ("ssm_scan",))
