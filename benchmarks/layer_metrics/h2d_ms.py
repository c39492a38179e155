"""Layer: host loop. Mean duration of the program's `h2d` span (around
`shard_batch`: placing the batch on the mesh) per iteration, over the counted
sparse blocks. Moves `examples_per_s`. Source: program_span."""

from benchmarks import span_reduce


def read(run):
    return span_reduce.host_ms(run, "h2d")
