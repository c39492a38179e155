"""Layer: host loop. Mean of the `data_wait` span's `ready` field over the
counted sparse blocks: the batches in the trainer's own prefetch queue
(depth 2) at the moment the loop asked for one. Near 0 the input path bounds
the step, near the depth the loop does. Moves `examples_per_s`.
Source: program_counter."""

from benchmarks import span_reduce


def read(run):
    r = span_reduce.reduced(run)
    return r["host"]["ready_mean"] if r and r["host"] else None
