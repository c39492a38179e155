"""Layer: model. Counter `moe_room_used` of the sparse trainer's `train`
records (the loss function's auxiliary output; `models/mellum2.room_used`):
the share of its room that an expert layer's load took, the worst layer, mean
over the counted blocks' log steps: the live rows over the sorted rows there
is room for (twice an even load's), or the fullest block of tokens' live rows
over its slots where that is more. An even load reads 0.5. Up to 1.0 a layer
summed its experts' rows back to tokens over the rows that are there; over 1.0
its sums, or with more live rows than room the whole layer, went by a row for
every one of the `T x top` assignments that step (no token is dropped at any
value). None where the program has no such counter. Moves `examples_per_s`.
Source: program_counter."""

from benchmarks import model_scopes


def read(run):
    return model_scopes.counter(run, "moe_room_used")
