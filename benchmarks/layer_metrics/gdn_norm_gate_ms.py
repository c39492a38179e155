"""Layer: model. Scope `gdn_norm_gate` inside `linear_attn` (`delta.normed_gate`): the
RMSNorm over each value head's 128 outputs times `silu(z)`, one pass over
`[tokens, 4096]` each way, XLA's fusions and no kernel, held apart from the
rule before it and `gdn_out_proj` after it by `optimization_barrier`s. Self time
of the device operations per step of the profiled sparse block, the chips' mean,
forward, recomputed and backward together. None where the trace names no such
scope. Moves `examples_per_s`. Source: device_trace."""

from benchmarks import gdn_ops


def read(run):
    return gdn_ops.scopes_ms(run, ("gdn_norm_gate",))
