"""Operations and bytes of the grouped products that the experts of
`models/mellum2.py` run through `lax.ragged_dot` (PR 31), which the TPU
compiler serves with a Mosaic kernel of its own (`ragged-dot-none`; it
carries no `op_name`, so no scope reads it). Counted from the rows the held
experts really got, the counter `moe_held_assignments` (a step's sum over
the layers): what the algorithm needs and no more, so a share of a roofline
cannot read high.

A row costs one multiply-add for every entry of its expert's three
matrices (`arch.expert_product_macs_per_assignment`: W1, W3 hidden x width,
W2 width x hidden), 2 operations each, in every pass over the rows. A layer
has three calls a pass, and the step four passes: forward, the forward
recomputed in the backward pass, the rows' cotangents, the weights'
cotangents. The bytes of a pass are each operand and result once: the rows
in and out in bfloat16 and the held experts' matrices (bfloat16 where they
are read, float32 where their cotangent is written: counted as bfloat16,
the smaller)."""

from __future__ import annotations

from benchmarks import model_scopes

KERNEL = "ragged-dot-none"
CALLS_PER_LAYER_AND_PASS = 3


def flops_per_pass(config: dict, held_rows: float) -> float:
    return 2.0 * config["arch"]["expert_product_macs_per_assignment"] \
        * held_rows


def bytes_per_pass(config: dict, held_rows: float) -> float:
    hidden, width = config["hidden_size"], config["moe_intermediate_size"]
    rows = held_rows * CALLS_PER_LAYER_AND_PASS * (hidden + width)
    weights = (config["num_hidden_layers"] * config["num_experts"]
               * config["arch"]["expert_product_macs_per_assignment"])
    return 2.0 * (rows + weights)


def roofline_share(run: dict):
    """The least time the kernel's calls of one step could take (the larger
    of operations over the bf16 peak and bytes over HBM bandwidth, for the
    passes its calls make) over their device time, in per cent; None where
    the trace has no such kernel or the program no such counter."""
    k = model_scopes.kernel(run, KERNEL)
    held = model_scopes.counter(run, "moe_held_assignments")
    if not k or not k["s_per_step"] or held is None:
        return None
    config = run["config"]
    passes = k["calls_per_step"] / (CALLS_PER_LAYER_AND_PASS
                                    * config["num_hidden_layers"])
    least = max(
        flops_per_pass(config, held) / run["peaks"]["bf16_flops_per_s"],
        bytes_per_pass(config, held) / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * passes * least / k["s_per_step"]
