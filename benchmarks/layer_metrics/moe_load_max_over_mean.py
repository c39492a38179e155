"""Layer: model. Counter `moe_load_max_over_mean` of the sparse trainer's
`train` records (the loss function's auxiliary output): the rows the fullest
held expert got over the held experts' mean, the worst layer, mean over the
counted blocks' log steps. 1 is an even load; no token is dropped at any
value, the fullest expert's product is that much longer. None where the
program has no such counter. Moves `examples_per_s`. Source: program_counter."""

from benchmarks import model_scopes


def read(run):
    return model_scopes.counter(run, "moe_load_max_over_mean")
