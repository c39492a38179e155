"""Layer: host loop. Mean duration of the program's `iteration` span less
what its children cover, per iteration, over the counted sparse blocks: the
part of the loop that still has no name. Moves `examples_per_s`.
Source: program_span."""

from benchmarks import span_reduce


def read(run):
    r = span_reduce.reduced(run)
    return 1e3 * r["host"]["self_s"] if r and r["host"] else None
