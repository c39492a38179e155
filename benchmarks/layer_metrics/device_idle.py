"""Layer: device. Share of the traced sparse block in which no operation ran
on the device: 1 - busy / window, averaged over the chips. The loop blocks on
every step, so this is what the host costs the chip. Moves `examples_per_s`.
Source: device_trace."""


def read(run):
    t = run.get("trace")
    if not t or "sparse" not in t["arms"]:
        return None
    a = t["arms"]["sparse"]
    return 100.0 * (1.0 - a["busy_s"] / a["window_s"])
