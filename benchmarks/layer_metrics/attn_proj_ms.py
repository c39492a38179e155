"""Layer: model. Scope `attn_proj` (`models/mellum2.Attention`): the q, k, v and
output projections, the rotary turn (scope `rope` inside it), the scaling and
the casts: attention WITHOUT `attn_window`/`attn_full`, which read attention
proper. The scope whole. Self time of the device operations per step of the
profiled sparse block, the chips' mean, forward, recomputed and backward
together (`scope_tree.py`). None where the trace names no such scope. Moves
`examples_per_s`. Source: device_trace."""

from benchmarks import scope_tree


def read(run):
    return scope_tree.under_ms(run, "attn_proj")
