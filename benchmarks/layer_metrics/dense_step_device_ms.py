"""Layer: dense step program. Union of the device-operation intervals per
step of the dense baseline in its traced block, averaged over the chips.
Moves `dense_examples_per_s`. Source: device_trace."""


def read(run):
    t = run.get("trace")
    if not t or "dense" not in t["arms"]:
        return None
    return 1e3 * t["arms"]["dense"]["busy_s_per_step"]
