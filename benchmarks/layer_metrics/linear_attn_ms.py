"""Layer: model. Scope `linear_attn` (`models/blocks/delta.GatedDeltaNet`): the gated
delta-rule mixer whole, in every `linear_attention` layer: `gdn_in_proj`,
`gdn_conv`, `gdn_rule`, `gdn_norm_gate` and `gdn_out_proj` beneath it, and what
lies directly under it. NOT the `input_norm` before it (`rms_norm_ms`). Self time
of the device operations per step of the profiled sparse block, the chips' mean,
forward, recomputed and backward together, by the innermost of the configuration's
`model_scopes` (`gdn_ops.py`). None where the trace names no such scope. Moves
`examples_per_s`. Source: device_trace."""

from benchmarks import gdn_ops


def read(run):
    return gdn_ops.scopes_ms(run, gdn_ops.SCOPES)
