"""Layer: host loop. Mean duration of the program's `step_dispatch` span
(around the call of the jitted step alone, not the wait for it) per
iteration, over the counted sparse blocks. Moves `examples_per_s`.
Source: program_span."""

from benchmarks import span_reduce


def read(run):
    return span_reduce.host_ms(run, "step_dispatch")
