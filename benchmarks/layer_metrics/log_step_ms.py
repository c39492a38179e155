"""Layer: host loop. Mean duration of the program's `log_step` span (around
`_log_train`, every `log_every`-th iteration) per OCCURRENCE, over the
counted sparse blocks: what makes every tenth iteration the tail. Moves
`step_ms_p95`. Source: program_span."""

from benchmarks import span_reduce


def read(run):
    return span_reduce.host_ms(run, "log_step", per="per_occurrence_s")
