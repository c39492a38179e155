"""Layer: host loop. Mean duration of the program's `data_wait` span (around
`next(it)` in `Trainer.train`) per iteration, over the counted sparse blocks
of a traced run: the inside twin of `data_wait_ms`. Moves `examples_per_s`.
Source: program_span."""

from benchmarks import span_reduce


def read(run):
    return span_reduce.host_ms(run, "data_wait")
