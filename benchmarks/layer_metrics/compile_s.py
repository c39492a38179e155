"""Layer: construction. Seconds inside the backend compiler (or reading its
persistent cache) during set-up, from `jax.monitoring`'s compile-duration
events. Moves `setup_s`. Source: program_counter (JAX's own events)."""


def read(run):
    return float(run["compile_s"])
