"""Layer: host loop. The dense baseline trainer's examples per second over
its blocks of the window (global batch x unskipped steps / the blocks' wall
time), in a cell whose step the host's input path bounds: there both
trainers' rates are the one Python loop's, so the dense rate guards nothing
that `examples_per_s` does not, and over a third of the window it swings
twice as far with every stall of a shared host. In such a cell it is no
end-to-end metric; it is reported here so that the sparse:dense ratio can
still be derived. Moves `examples_per_s`. Source: host_clock."""


def read(run):
    return run["totals"]["dense"]["examples_per_s"] or None
