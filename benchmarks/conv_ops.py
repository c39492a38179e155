"""Operations and bytes of the gated short convolution's elementwise pass
(`models/lfm2_moe.ShortConv`, scope `conv_gate`; PR 38), and the readings of
the mixer's scopes. Counted from shapes: what the algorithm needs and no
more, so a share of a roofline cannot read high.

Between the mixer's two products lies one pass over `[tokens, hidden]`: the
first gate `v = B * x`, the three taps `c_t = k_0 v_{t-2} + k_1 v_{t-1} +
k_2 v_t`, the second gate `y = C * c`. No kernel: XLA's fusions, held apart
from the products by an `optimization_barrier` so that the scope holds the
pass whole. A (token, channel) costs

  forward     reads B, C, x (bfloat16), writes y             8 bytes, 7 ops
  backward    reads dy, B, C, x, writes dB, dC, dx          14 bytes, 15 ops

and the forward pass runs a second time where the layer is recomputed in
its backward pass (`nn.remat`; whether it is, the trace says: operations
under `rematted_computation`). The taps' own gradient (`[hidden, 3]`) and
the zeros before the sequence starts are left out. At 7 operations to 8
bytes the pass is bound by memory on any chip (a v5e turns 240 operations a
byte), so its roofline is the bytes over HBM bandwidth.

Where the trace names none of the scopes (a parent commit, another model's
cell) every function returns None, and nothing raises.
"""

from __future__ import annotations

from typing import Optional, Sequence

from benchmarks import model_scopes, scope_tree

# the mixer's scopes: `short_conv` around the three beneath it (its own
# operations are what lies directly under it: reshapes, if any)
SCOPES = ("short_conv", "conv_in_proj", "conv_gate", "conv_out_proj")
FORWARD_BYTES, BACKWARD_BYTES = 8, 14
FORWARD_OPS, BACKWARD_OPS = 7, 15


def scopes_ms(run: dict, names: Sequence[str]) -> Optional[float]:
    """Milliseconds per step of the operations whose innermost scope of the
    configuration's `model_scopes` is one of `names`; None where the trace
    names none of them."""
    found = [ms for ms in (model_scopes.scope_ms(run, n) for n in names)
             if ms is not None]
    return sum(found) if found else None


def elements_per_step(run: dict) -> Optional[int]:
    """(token, channel) pairs that one chip's conv layers go over a step."""
    config = run["config"]
    layers = config.get("arch", {}).get("conv_layers")
    if not layers:
        return None
    sequences = run["global_batch"]["sparse"] // run["cell"]["chips"]
    return (layers * sequences * config["arch"]["sequence_length"]
            * config["hidden_size"])


def _per_step(run: dict, forward_cost: int, backward_cost: int
              ) -> Optional[int]:
    """`forward_cost` an element for each forward pass of a step (two where
    the trace holds recomputed operations) and `backward_cost` for the
    backward pass."""
    n = elements_per_step(run)
    if n is None:
        return None
    forward = 2 if scope_tree.pass_ms(run, "recomputed") else 1
    return n * (forward * forward_cost + backward_cost)


def gate_bytes_per_step(run: dict) -> Optional[int]:
    return _per_step(run, FORWARD_BYTES, BACKWARD_BYTES)


def gate_ops_per_step(run: dict) -> Optional[int]:
    return _per_step(run, FORWARD_OPS, BACKWARD_OPS)


def gate_roofline_share(run: dict) -> Optional[float]:
    """The least time the `conv_gate` passes of one step could take (the
    larger of bytes over HBM bandwidth and operations over the bf16 peak;
    the bytes, by far) over their device time, in per cent."""
    ms = scopes_ms(run, ("conv_gate",))
    need = gate_bytes_per_step(run)
    if not ms or need is None:
        return None
    least = max(need / run["peaks"]["hbm_bytes_per_s"],
                gate_ops_per_step(run) / run["peaks"]["bf16_flops_per_s"])
    return 100.0 * least / (ms / 1e3)
