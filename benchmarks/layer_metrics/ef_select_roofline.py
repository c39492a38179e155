"""Layer: EF and select kernel. The least time the pass could take, which is
its bytes through HBM (`bytes.py`: 4 per element of the padded flat gradient
for each full-length array that the kernel's HLO line in this run's trace
shows in HBM; two of three on record, the gradient operand is in `S(1)`)
over the chip's HBM bandwidth (it is bound by memory, not by operations),
over the kernel's device time. Cannot pass 100%. Nothing to read where no
full-length array is in HBM. Moves `examples_per_s`. Source: device_trace."""

from benchmarks import bytes as byte_counts
from benchmarks import trace_reduce


def read(run):
    t = run.get("trace")
    if not t or "sparse" not in t["arms"]:
        return None
    arm = t["arms"]["sparse"]
    s = arm.get("kernel_s_per_step")
    if not s or len(arm["kernel_hlo"]) != 1:
        return None
    passes, numel = trace_reduce.hbm_passes(arm["kernel_hlo"][0])
    if not passes:
        return None
    if numel != run["ef_numel"]:
        raise ValueError(f"the kernel's largest array has {numel} elements, "
                         f"the flat gradient {run['ef_numel']}")
    least = byte_counts.ef_select_bytes(numel, passes) / run["peaks"][
        "hbm_bytes_per_s"]
    return 100.0 * least / s
