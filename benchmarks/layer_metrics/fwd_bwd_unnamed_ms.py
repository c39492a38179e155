"""Layer: model. Under `fwd_bwd` and under NO name of a model's
(`scope_tree.MODEL_NAMES`): the residual additions, the counters, a scanned
body's slicing and stacking, what XLA names after nothing finer. With it
`fwd_bwd_ms` is the sum of its model scopes. Self time of those device
operations per step of the profiled sparse block, the chips' mean. Moves
`examples_per_s`. Source: device_trace."""

from benchmarks import scope_tree


def read(run):
    return scope_tree.unnamed_ms(run)
