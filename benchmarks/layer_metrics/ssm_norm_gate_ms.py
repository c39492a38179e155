"""Layer: model. Scope `ssm_norm_gate` inside `ssm` (`ssm.gated_group_norm`): `y
silu(z)` under an RMSNorm over each group's 512 columns, one pass over `[tokens,
4096]` each way, XLA's fusions and no kernel, held apart from the scan before it
and `ssm_out_proj` after it by `optimization_barrier`s. Self time of the device
operations per step of the profiled sparse block, the chips' mean, forward,
recomputed and backward together. None where the trace names no such scope. Moves
`examples_per_s`. Source: device_trace."""

from benchmarks import ssd_ops


def read(run):
    return ssd_ops.scopes_ms(run, ("ssm_norm_gate",))
