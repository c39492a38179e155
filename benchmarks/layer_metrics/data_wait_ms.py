"""Layer: host loop. Mean time the sparse trainer's loop spent inside the
timed iterator's `next()` per step, over the whole window: what the loop
waited for its input. Moves `examples_per_s`. Source: host_clock."""


def read(run):
    waits = run["totals"]["sparse"]["wait_s"]
    return 1e3 * sum(waits) / len(waits) if waits else None
