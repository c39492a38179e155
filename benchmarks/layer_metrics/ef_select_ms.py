"""Layer: EF and select kernel. Device time of the sparse step's one
`tpu_custom_call` (the fused error-feedback and candidate-select Mosaic
kernel) per step, averaged over the chips. Moves `examples_per_s`.
Source: device_trace."""


def read(run):
    t = run.get("trace")
    if not t or "sparse" not in t["arms"]:
        return None
    s = t["arms"]["sparse"].get("kernel_s_per_step")
    return 1e3 * s if s else None
