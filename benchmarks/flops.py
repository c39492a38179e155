"""Operations a training step needs, counted from layer shapes.

The count is the algorithm's, not the compiled program's: every multiply-add
of a convolution or a dense layer is 2 operations, a training step is the
forward pass and twice as much again for the two backward products, nothing
recomputed. Batch norm, ReLU, pooling, the loss and the optimizer are left
out (under 1% for these networks), so a utilisation worked out from this
count cannot read high. XLA's `cost_analysis` is not used: it counts the
program it compiled, about twice this for ResNet-50 (BASELINE.md).

A configuration's file lists its matrix products under `matmul_layers`: for
each, the output `positions` per example (height x width, or tokens), the
reduction length `k` (kernel height x width x input channels) and the output
width `n`.
"""

from __future__ import annotations


def forward_macs_per_example(config: dict) -> int:
    return sum(l["positions"] * l["k"] * l["n"]
               for l in config["matmul_layers"])


def train_flops_per_example(config: dict) -> int:
    """Forward and backward: 3 x (2 operations per multiply-add)."""
    return 3 * 2 * forward_macs_per_example(config)


def train_flops_per_step(config: dict, examples: int) -> int:
    return train_flops_per_example(config) * examples
