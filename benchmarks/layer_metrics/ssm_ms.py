"""Layer: model. Scope `ssm` (`models/blocks/ssm.Mamba2Mixer`): the Mamba-2
state-space mixer whole, in every `M` block: `ssm_in_proj`, `ssm_conv`, `ssm_scan`,
`ssm_norm_gate` and `ssm_out_proj` beneath it, and what lies directly under it.
NOT the block's norm before it (`rms_norm_ms`). Self time of the device
operations per step of the profiled sparse block, the chips' mean, forward,
recomputed and backward together, by the innermost of the configuration's
`model_scopes` (`ssd_ops.py`). None where the trace names no such scope. Moves
`examples_per_s`. Source: device_trace."""

from benchmarks import ssd_ops


def read(run):
    return ssd_ops.scopes_ms(run, ssd_ops.SCOPES)
