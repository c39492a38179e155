"""Layer: dense step program. `dense_step_device_ms` in a cell that does not
report `dense_examples_per_s` end to end (the host bounds its step): the
dense program's forward, backward and update are the sparse step's too, so
there it moves `examples_per_s`. Source: device_trace."""

from benchmarks.layer_metrics.dense_step_device_ms import read  # noqa: F401
