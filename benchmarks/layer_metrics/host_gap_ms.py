"""Layer: host loop. Mean sparse loop iteration (host clock, whole window)
less the mean time the device was busy per sparse step (trace): what a step
costs beyond the device's work, i.e. dispatch, the blocking wait's return,
placing the batch, logging. Moves `examples_per_s`. Source: device_trace."""


def read(run):
    t = run.get("trace")
    iters = run["totals"]["sparse"]["iter_s"]
    if not t or "sparse" not in t["arms"] or not iters:
        return None
    return 1e3 * (sum(iters) / len(iters)
                  - t["arms"]["sparse"]["busy_s_per_step"])
