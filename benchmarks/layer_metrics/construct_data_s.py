"""Layer: construction. Seconds inside the program's `build_data` span (both
datasets of `Trainer.__init__`), summed over the run's two trainers. Moves
`setup_s`. Source: program_span."""

from benchmarks import span_reduce


def read(run):
    r = span_reduce.reduced(run)
    return r["setup"].get("build_data") if r and r["setup"] else None
