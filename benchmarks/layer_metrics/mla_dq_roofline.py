"""Layer: attention kernels. Latent attention's splash kernel
`splash_mha_dq_no_residuals`, the queries' cotangent (q k^T and ds k at 192,
do v^T at 128): the least time its calls of one step could take
(`mla_ops.py`: operations of the (query, key) pairs the causal mask lets
through, each product at its own head size and nothing padded, over the
chip's bf16 peak; it is bound by operations, its bytes over HBM bandwidth
are the smaller) over their device time. Cannot pass 100%. None where the
trace has no such kernel. Moves `examples_per_s`. Source: device_trace."""

from benchmarks import mla_ops


def read(run):
    return mla_ops.roofline_share(run, "splash_mha_dq_no_residuals")
