"""Flat sparse-aware SGD(+momentum, +weight-decay) — the TPU-first
optimizer path for compressed exchanges.

Why it exists. After the gradient the step is elementwise work over
vectors of n parameters, so its cost is how often it streams one (at
n = 340 M a pass is 1.36 GB, 1.96 ms on a v5e; ``scripts/aot_preflight.py``
counts the passes of the compiled program by scope, PERF.md section 5 has
the table). The gradient is k-sparse; the only DENSE consumer is the
momentum buffer. So scatter the gathered (index, value) pairs **directly
into the decayed momentum**, in place:

    m' = mu * m (+ wd * p)          # the pass every SGD step already pays
    m'[idx] += val                  # k-sized in-place scatter-add
    p' = p - lr(step) * m'          # leaf by leaf; -lr*m' is no vector

vs the generic path's ``zeros(n).at[idx].add(val)`` (n-sized write) +
optax reading that buffer back (n-sized read) + its updates as a pytree of
their own — identical math (scatter-add commutes with the elementwise
decay; duplicate indices from different workers sum exactly as the dense
accumulation would).

The state is ONE flat momentum buffer beside a pytree of parameters, and a
leaf's device layout is tiled by its own shape while the flat buffer is
linear: wherever the two meet, XLA runs a ``reshape`` that is a pass of
its own. So each leaf crosses ONCE each way: ``flat_leaves`` brings it to
a vector, ``decay`` and ``apply`` work on vectors against slices of the
momentum, and ``apply``'s last reshape writes the leaf back. Nothing is
concatenated (a concatenation compiles to one more copy of every piece)
and no n-vector exists beside the momentum: 10.4 passes under the scope
``update`` where the parameters' ravel, ``-lr*m'`` as a vector and its
unravel took 10.3 + 1.4 and the old-or-new selects 8 more (PR 32).

The reference reaches the same concern through torch's optimizer hooks
(SURVEY.md §2 C2: the distributed optimizer owns the update); here it is a
functional transform on the SAME flat buffer the exchange already uses.
The dense (warm-up) path uses the identical state and update rule —
``m' = mu*m (+wd*p) + g_dense`` — so warm-up -> sparse transitions carry
momentum with no state conversion. Both are traced inside the commit side
of ``trainstep._commit``'s ``cond``, so they write the donated buffers in
place and a skipped step runs none of it.

Not expressible here (callers fall back to the optax path): nesterov
(needs the pre-decay gradient densely), optax chains beyond
wd+momentum+lr, and hierarchical meshes whose outer (DCN) axes psum a
dense partial — there the dense buffer must exist anyway.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
from jax import lax


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

    def flat_leaves(self, params: Any) -> list:
        """Each leaf as a vector, in tree_leaves (== ravel_pytree) order.

        A leaf's device layout is tiled by its own shape and the momentum
        is linear, so this reshape is a pass of its own whatever is
        written here; the barrier keeps it the ONLY one: without it XLA
        sinks the reshape below the elementwise work and then
        materialises wd*p and lr*m' as vectors of their own (16.2 passes
        under ``update`` for 10.4, compiled for a v5e at n = 340 M)."""
        with jax.named_scope("update"):
            return [lax.optimization_barrier(p.reshape(-1))
                    for p in jax.tree_util.tree_leaves(params)]

    def decay(self, m: jax.Array, flat_leaves: list,
              flat_g: Optional[jax.Array] = None) -> tuple:
        """The dense half of the update, mu*m (+ wd*p) (+ g), written
        into the momentum buffer itself: with a weight decay leaf by leaf
        (each leaf's wd*p goes to that leaf's slice, so the parameters are
        never concatenated), else in one pass. Returns the buffer and its
        leaves' new slices as they were computed."""
        with jax.named_scope("update"):
            def decayed(part, flat_p, g):
                part = part * self.momentum if self.momentum \
                    else jnp.zeros_like(part)
                if flat_p is not None:
                    part = part + self.weight_decay * flat_p.astype(m.dtype)
                return part if g is None else part + g.astype(m.dtype)

            if not self.weight_decay:
                m = decayed(m, None, flat_g)
                return m, self.slices(m, flat_leaves)
            parts, off = [], 0
            for flat_p in flat_leaves:
                window = (off,), (off + flat_p.size,)
                parts.append(decayed(
                    lax.slice(m, *window), flat_p,
                    None if flat_g is None else lax.slice(flat_g, *window)))
                m = lax.dynamic_update_slice(m, parts[-1], (off,))
                off += flat_p.size
            return m, parts

    @staticmethod
    def slices(m: jax.Array, flat_leaves: list) -> list:
        """Each leaf's slice of the momentum."""
        offs = [0]
        for flat_p in flat_leaves:
            offs.append(offs[-1] + flat_p.size)
        return [lax.slice(m, (a,), (b,)) for a, b in zip(offs, offs[1:])]

    def apply(self, params: Any, flat_leaves: list, m_slices: list,
              step: jax.Array) -> Any:
        """p - lr(step)*m', leaf by leaf against that leaf's slice of the
        updated momentum, so ``-lr*m'`` never exists as a vector of its
        own; the last reshape writes the leaf back."""
        with jax.named_scope("update"):
            lr = self.lr_at(step)
            leaves, treedef = jax.tree_util.tree_flatten(params)
            return jax.tree_util.tree_unflatten(treedef, [
                (flat_p - lr * part).astype(p.dtype).reshape(p.shape)
                for p, flat_p, part in zip(leaves, flat_leaves, m_slices)])

    def sparse_step(self, params: Any, m: jax.Array, idx: jax.Array,
                    val: jax.Array, step: jax.Array) -> tuple:
        """(params', m') from gathered (idx, val) pairs — the pairs'
        values must already carry the /P average. Padding slots
        (0, 0.0) add zero at index 0: harmless, same as decompression."""
        flat_leaves = self.flat_leaves(params)
        m, _ = self.decay(m, flat_leaves)
        with jax.named_scope("scatter"):
            m = m.at[idx].add(val.astype(m.dtype).reshape(-1), mode="drop")
        return self.apply(params, flat_leaves,
                          self.slices(m, flat_leaves), step), m

    def dense_step(self, params: Any, m: jax.Array, flat_g: jax.Array,
                   step: jax.Array) -> tuple:
        """(params', m') from an (averaged) dense flat gradient."""
        flat_leaves = self.flat_leaves(params)
        m, m_slices = self.decay(m, flat_leaves, flat_g)
        return self.apply(params, flat_leaves, m_slices, step), m
