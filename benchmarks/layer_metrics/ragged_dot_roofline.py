"""Layer: expert kernels. The grouped-product kernel `ragged-dot-none`: the
least time its calls of one step could take (`moe_ops.py`: 2 operations a
multiply-add of the rows the held experts really got, the counter
`moe_held_assignments`, over the chip's bf16 peak; it is bound by
operations, its bytes over HBM bandwidth are the smaller) over their device
time. Cannot pass 100%. None where the trace has no such kernel or the
program no such counter. Moves `examples_per_s`. Source: device_trace."""

from benchmarks import moe_ops


def read(run):
    return moe_ops.roofline_share(run)
