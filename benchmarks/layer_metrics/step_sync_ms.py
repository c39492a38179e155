"""Layer: host loop. Mean duration of the program's `step_sync` span (around
`jax.block_until_ready(m.loss)`: the device's step as the host waits for it,
and the return) per iteration, over the counted sparse blocks. Moves
`examples_per_s`. Source: program_span."""

from benchmarks import span_reduce


def read(run):
    return span_reduce.host_ms(run, "step_sync")
