"""Layer: model. Scope `rms_norm` (`models/mellum2.RMSNorm`, one name for every
instance): the layers' `input_norm` and `post_attn_norm` and the final `norm`
(a prediction module's three where one is built); NOT the two norms inside
latent attention's bottlenecks, which `mla_q_ms` and `mla_kv_ms` hold. Self
time of the device operations per step of the profiled sparse block, the
chips' mean, forward, recomputed and backward together (`scope_tree.py`). None
where the trace names no such scope. Moves `examples_per_s`. Source:
device_trace."""

from benchmarks import scope_tree


def read(run):
    return scope_tree.ms(run, "rms_norm", without=("mla_q", "mla_kv"))
