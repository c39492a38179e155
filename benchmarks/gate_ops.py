"""Bytes of the gate on the attention's output (`models/mellum2.gated_output`,
scope `attn_gate` inside `attn_proj`; PR 40), and the reading of its scope.
Counted from shapes: what the algorithm needs and no more, so a share of a
roofline cannot read high.

Between the attention kernel and `o_proj` lies one pass over `[tokens, heads
x head_dim]`: `y = o * sigmoid(g)`, entry by entry. No kernel: XLA's
fusions, held apart from the kernel and the products around them by
`optimization_barrier`s so that the scope holds the pass whole. An entry
costs

  forward     reads o, g (bfloat16), writes y                    6 bytes
  backward    reads dy, o, g, writes do, dg                     10 bytes

and the forward pass runs a second time where the layer is recomputed in
its backward pass (`nn.remat`; whether it is, the trace says: operations
under `rematted_computation`). At about ten operations to six bytes the
pass is bound by memory on any chip (a v5e turns 240 operations a byte), so
its roofline is the bytes over HBM bandwidth. The counter's mean of the
sigmoid is not counted: where XLA gives it a pass of its own the share
reads lower.

Where the trace names no such scope (a parent commit, another model's cell)
or the configuration no `arch.gated_attention_layers`, every function
returns None, and nothing raises.
"""

from __future__ import annotations

from typing import Optional

from benchmarks import model_scopes, scope_tree

SCOPE = "attn_gate"
FORWARD_BYTES, BACKWARD_BYTES = 6, 10


def gate_ms(run: dict) -> Optional[float]:
    """Milliseconds per step of the operations whose innermost scope of the
    configuration's `model_scopes` is `attn_gate`."""
    return model_scopes.scope_ms(run, SCOPE)


def elements_per_step(run: dict) -> Optional[int]:
    """Entries of the attention's output that one chip's gated layers go
    over a step."""
    config = run["config"]
    layers = config.get("arch", {}).get("gated_attention_layers")
    if not layers:
        return None
    sequences = run["global_batch"]["sparse"] // run["cell"]["chips"]
    return (layers * sequences * config["arch"]["sequence_length"]
            * config["num_attention_heads"] * config["head_dim"])


def gate_bytes_per_step(run: dict) -> Optional[int]:
    """`FORWARD_BYTES` an entry for each forward pass of a step (two where
    the trace holds recomputed operations) and `BACKWARD_BYTES` for the
    backward pass."""
    n = elements_per_step(run)
    if n is None:
        return None
    forward = 2 if scope_tree.pass_ms(run, "recomputed") else 1
    return n * (forward * FORWARD_BYTES + BACKWARD_BYTES)


def gate_roofline_share(run: dict) -> Optional[float]:
    """The least time the `attn_gate` passes of one step could take (their
    bytes over HBM bandwidth) over their device time, in per cent."""
    ms = gate_ms(run)
    need = gate_bytes_per_step(run)
    if not ms or need is None:
        return None
    return 100.0 * (need / run["peaks"]["hbm_bytes_per_s"]) / (ms / 1e3)
