"""The keep-mask of the program's one dropout layer, drawn as the program
draws it, so that the reference sees the same units dropped.

This is knowledge of the program's plumbing, kept out of the reference (which
only takes a mask): the trainer splits `PRNGKey(seed)` three ways and keeps
the third as the state's key; a step folds in the step index, then 0 (the
data stream), then the worker's index; flax hands the layer the 'dropout'
stream of its own scope, `Dropout_0`, first draw. A change to any of that
shows as `correct: false` in the cells of a configuration with dropout.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp


class _Scope(nn.Module):
    rate: float

    @nn.compact
    def __call__(self, x):
        return nn.Dropout(self.rate, deterministic=False)(x)


def program_keep_mask(seed: int, step: int, worker: int, shape, rate: float):
    """float32 `shape`: 1/(1-rate) where the unit is kept, 0 where dropped."""
    state_key = jax.random.split(jax.random.PRNGKey(seed), 3)[2]
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(state_key, step), 0), worker)
    return _Scope(rate).apply({}, jnp.ones(shape, jnp.float32),
                              rngs={"dropout": key})
