"""Learning-rate schedules.

Reference parity: ``_adjust_learning_rate`` in ``dl_trainer.py``
(SURVEY.md §2 C5): milestone step-decay by ``lr_decay``, with the
multi-worker *gradual warmup* of Goyal et al. — linear ramp from the
single-worker lr to ``lr * nworkers`` over the first ``warmup_epochs``
(SURVEY.md §2.3 "LR also warm-up-scales with worker count").

Built as an optax schedule (step -> lr) so it lives inside the jitted train
step; no Python-side lr mutation.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax.numpy as jnp
import numpy as np


def warmup_milestone_schedule(base_lr: float, nworkers: int,
                              steps_per_epoch: int, total_steps: int,
                              warmup_epochs: float = 5.0,
                              milestones: Sequence[float] = (0.5, 0.75),
                              decay: float = 0.1) -> Callable:
    """step -> lr. Ramp base_lr -> base_lr*nworkers, then milestone decay.

    ``milestones`` are fractions of ``total_steps`` (e.g. the reference's
    epoch-{41,61} decays for 80-epoch CIFAR runs ~ (0.5, 0.75)).
    """
    peak = base_lr * max(1, nworkers)
    warmup_steps = max(1, int(warmup_epochs * steps_per_epoch))
    host_boundaries = np.asarray([int(m * total_steps) for m in milestones])
    boundaries = jnp.asarray(host_boundaries)

    def schedule(step):
        # A Python number (the trainer's log line) is worked out on the
        # host: the same expression in eager jnp is a handful of small
        # device programs, and they queue behind a step in flight and hold
        # the caller until it has ended. An array or a tracer (inside the
        # jitted step) takes jnp.
        xp, bnd = ((np, host_boundaries) if isinstance(step, (int, float))
                   else (jnp, boundaries))
        step = xp.asarray(step, xp.float32)
        frac = xp.clip(step / warmup_steps, 0.0, 1.0)
        lr = base_lr + (peak - base_lr) * frac if nworkers > 1 else xp.full_like(
            frac, base_lr)
        n_decays = xp.sum(step >= bnd)
        return lr * (decay ** n_decays)

    return schedule
