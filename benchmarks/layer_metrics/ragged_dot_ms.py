"""Layer: expert kernels. The experts' grouped products: XLA's own Mosaic
kernel for `lax.ragged_dot`, `ragged-dot-none` (`moe_ops.py`), device time
of its calls per step of the profiled sparse block (three a layer and pass;
forward, recomputed forward and both backward passes), by the kernel's name:
the compiler gives it no `op_name`. Also inside `moe_experts_ms`. None where
the trace has no such kernel. Moves `examples_per_s`.
Source: device_trace."""

from benchmarks import model_scopes, moe_ops


def read(run):
    k = model_scopes.kernel(run, moe_ops.KERNEL)
    return 1e3 * k["s_per_step"] if k else None
