"""Layer: model. Scope `short_conv` (`models/lfm2_moe.ShortConv`): the gated short
convolution whole, in every `conv` layer: `conv_in_proj` (2048 -> 3 x 2048),
`conv_gate` (both gates and the three taps) and `conv_out_proj` beneath it, and
what lies directly under it. NOT the `operator_norm` before it (`rms_norm_ms`).
Self time of the device operations per step of the profiled sparse block, the
chips' mean, forward, recomputed and backward together, by the innermost of the
configuration's `model_scopes` (`conv_ops.py`). None where the trace names no
such scope. Moves `examples_per_s`. Source: device_trace."""

from benchmarks import conv_ops


def read(run):
    return conv_ops.scopes_ms(run, conv_ops.SCOPES)
