"""Layer: host loop. Mean duration of the program's `step_readback` span
(`device_get` of `skipped`, `nonfinite` and `loss`, the throughput tracker,
the resilience monitor) per iteration, over the counted sparse blocks. Moves
`examples_per_s`. Source: program_span."""

from benchmarks import span_reduce


def read(run):
    return span_reduce.host_ms(run, "step_readback")
