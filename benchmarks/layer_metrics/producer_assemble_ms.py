"""Layer: host loop. Median of the `data_wait` span's `assemble_ms` over the
counted sparse blocks: what the producer thread took for the newest batch it
pulled (`data/loader.Prefetcher.assemble_s`). `input_ready_batches` saturates
at the queue's depth whenever the producer is faster than the step; this one
shows the headroom that is left (the step's time less it). None where no
`data_wait` span has the field (a program from before PR 35, a stream without
a producer thread). Moves `examples_per_s`. Source: program_counter."""

import statistics

from benchmarks import span_reduce


def read(run):
    rec = span_reduce.spans_of("sparse")
    if rec is None:
        return None
    spans = list(rec.spans)
    took = [s.fields["assemble_ms"] for b in run["blocks"]["sparse"]
            for s in span_reduce.in_block(spans, b)
            if s.name == "data_wait" and "assemble_ms" in s.fields]
    return statistics.median(took) if took else None
