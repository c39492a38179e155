"""Flat sparse-aware SGD(+momentum, +weight-decay) — the TPU-first
optimizer path for compressed exchanges.

Why it exists (r5 overhead decomposition; the artifacts are gone, the
scopes ``update`` and ``scatter`` price it on the chip now): after the r5
kernel work the sparse step's largest remaining term is the EF/exchange
floor, and a full HBM pass of it is the *decompression* detour — scatter
the gathered (index, value) pairs into a zeros buffer, hand the dense result to optax,
which immediately streams it back in to form the momentum update. The
gradient is k-sparse; the only DENSE consumer is the momentum buffer. So
scatter the pairs **directly into the decayed momentum**:

    m' = mu * m (+ wd * p)          # the pass every SGD step already pays
    m'[idx] += val                  # k-sized in-place scatter-add
    p  = p - lr(step) * m'          # unchanged

vs the generic path's ``zeros(n).at[idx].add(val)`` (n-sized write) +
optax reading that buffer back (n-sized read) — one full round-trip of the
model size saved per step, identical math (scatter-add commutes with the
elementwise decay; duplicate indices from different workers sum exactly as
the dense accumulation would).

The reference reaches the same concern through torch's optimizer hooks
(SURVEY.md §2 C2: the distributed optimizer owns the update); here it is a
20-line functional transform on the SAME flat buffer the exchange already
uses. The dense (warm-up) path uses the identical state and update rule —
``m' = mu*m (+wd*p) + g_dense`` — so warm-up -> sparse transitions carry
momentum with no state conversion.

Not expressible here (callers fall back to the optax path): nesterov
(needs the pre-decay gradient densely), optax chains beyond
wd+momentum+lr, and hierarchical meshes whose outer (DCN) axes psum a
dense partial — there the dense buffer must exist anyway.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp


class FlatSGDM(NamedTuple):
    """Config for the flat sparse-aware SGD update."""

    lr: Union[float, Callable[[jax.Array], jax.Array]]  # value or step->lr
    momentum: float = 0.0
    weight_decay: float = 0.0

    def lr_at(self, step: jax.Array) -> jax.Array:
        return self.lr(step) if callable(self.lr) else jnp.float32(self.lr)

    def init(self, n: int, dtype=jnp.float32) -> dict:
        """Optimizer state: ONE flat momentum buffer (replicated)."""
        return {"m": jnp.zeros((n,), dtype)}

    def decay(self, m: jax.Array,
              flat_params: Optional[jax.Array]) -> jax.Array:
        """The dense half of the update: mu*m (+ wd*p)."""
        with jax.named_scope("update"):
            m = m * self.momentum if self.momentum else jnp.zeros_like(m)
            if self.weight_decay:
                # internal invariant: both callers gate on _flat_params_if_wd
                assert flat_params is not None  # gklint: disable=fail-loud -- narrowing assert; callers gate on _flat_params_if_wd
                m = m + self.weight_decay * flat_params.astype(m.dtype)
            return m

    def sparse_step(self, m: jax.Array, idx: jax.Array, val: jax.Array,
                    flat_params: Optional[jax.Array],
                    step: jax.Array) -> tuple:
        """(flat_updates, m') from gathered (idx, val) pairs — the pairs'
        values must already carry the /P average. Padding slots
        (0, 0.0) add zero at index 0: harmless, same as decompression."""
        decayed = self.decay(m, flat_params)
        with jax.named_scope("scatter"):
            m_new = decayed.at[idx].add(
                val.astype(m.dtype).reshape(-1), mode="drop")
        with jax.named_scope("update"):
            return -self.lr_at(step) * m_new, m_new

    def dense_step(self, m: jax.Array, flat_g: jax.Array,
                   flat_params: Optional[jax.Array],
                   step: jax.Array) -> tuple:
        """(flat_updates, m') from an (averaged) dense flat gradient."""
        with jax.named_scope("update"):
            m_new = self.decay(m, flat_params) + flat_g.astype(m.dtype)
            return -self.lr_at(step) * m_new, m_new
