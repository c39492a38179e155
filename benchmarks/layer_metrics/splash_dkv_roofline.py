"""Layer: attention kernels. The splash-attention kernel `splash_mqa_dkv_no_residuals`,
backward for keys and values (q k^T, do v^T, p^T do, ds^T q): the least time its calls of one step could take
(`attn_ops.py`: operations of the (query, key) pairs the masks let through
over the chip's bf16 peak; it is bound by operations, its bytes over HBM
bandwidth are the smaller) over their device time. Cannot pass 100%. None
where the trace has no such kernel. Moves `examples_per_s`.
Source: device_trace."""

from benchmarks import attn_ops


def read(run):
    return attn_ops.roofline_share(run, "splash_mqa_dkv_no_residuals")
