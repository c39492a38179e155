"""Layer: host loop. Share of the counted sparse blocks' `step_sync` spans
that opened with a later step program already dispatched (the span's field
`ahead` >= 1): how often the loop had the device's next program queued
while it waited for the running one. A loop that waits for every step
before it dispatches the next reads 0; one that keeps a step in flight reads
all steps but a `train(n)` call's last. None where no `step_sync` span has
the field (a program from before PR 28). Moves `examples_per_s`.
Source: program_counter."""

from benchmarks import span_reduce


def read(run):
    rec = span_reduce.spans_of("sparse")
    if rec is None:
        return None
    spans = list(rec.spans)
    ahead = [s.fields["ahead"] for b in run["blocks"]["sparse"]
             for s in span_reduce.in_block(spans, b)
             if s.name == "step_sync" and "ahead" in s.fields]
    if not ahead:
        return None
    return 100.0 * sum(1 for a in ahead if a >= 1) / len(ahead)
