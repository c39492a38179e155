"""Layer: sparse step program. Union of the device-operation intervals per
sparse step in the traced block, averaged over the chips. Moves
`examples_per_s`. Source: device_trace."""


def read(run):
    t = run.get("trace")
    if not t or "sparse" not in t["arms"]:
        return None
    return 1e3 * t["arms"]["sparse"]["busy_s_per_step"]
