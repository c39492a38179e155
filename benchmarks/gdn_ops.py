"""Operations and bytes of the gated delta-rule mixer
(`models/blocks/delta.GatedDeltaNet`, scope `linear_attn`; PR 44), and the
readings of its scopes. Counted from shapes and from the RECURRENCE, not from
what implements it: what the algorithm needs and no more, so a share of a
roofline cannot read high.

The mixer's scopes: `gdn_in_proj` (one product to `[q | k | v | z]`, a small
one to `[b | a]`), `gdn_conv` (a depthwise causal convolution of a few taps
with SiLU over `[q | k | v]`), `gdn_rule` (gates, L2 norms and the rule),
`gdn_norm_gate` (an RMSNorm over each head's output times `silu(z)`),
`gdn_out_proj`; `linear_attn` around them holds what lies directly under it.

The rule, a token and value head, with a state of `d_k x d_v`: `S k`, the
rank-one write and `S q`, three products of `d_k x d_v`, 2 operations a
multiply-add; twice that backward; the forward again where the layer is
recomputed in its backward pass (`nn.remat`; whether it is, the trace says:
operations under `rematted_computation`). The chunked form that runs on the
chip does more (the chunk's `K K^T`, `Q K^T`, the solve) and none of the
more is counted. Its bytes a token: q and k (a key head's, bfloat16), v read
and o written (a value head's, bfloat16), g and beta (float32) forward; the
same read again, the output's cotangent read and five cotangents written
backward. The state never crosses memory in this count.

`gdn_conv` and `gdn_norm_gate` are passes over `[tokens, channels]` bound by
memory on any chip, counted as `conv_ops.py` counts `conv_gate`:

  gdn_conv       forward  reads x, writes y (bfloat16)           4 bytes
                 backward reads dy, x, writes dx                  6 bytes
  gdn_norm_gate  forward  reads o, z, writes y (bfloat16)         6 bytes
                 backward reads dy, o, z, writes do, dz          10 bytes

Where the trace names none of the scopes (a parent commit, another model's
cell) or the configuration no `arch.linear_layers`, every function returns
None, and nothing raises.
"""

from __future__ import annotations

from typing import Optional

from benchmarks import conv_ops, scope_tree

SCOPES = ("linear_attn", "gdn_in_proj", "gdn_conv", "gdn_rule",
          "gdn_norm_gate", "gdn_out_proj")
CONV_BYTES = (4, 6)             # forward, backward, a token and channel
NORM_GATE_BYTES = (6, 10)
RULE_PRODUCTS = 3               # S k, the write, S q

scopes_ms = conv_ops.scopes_ms


def _tokens(run: dict) -> Optional[int]:
    """Tokens that one chip's linear layers go over a step, all layers."""
    layers = run["config"].get("arch", {}).get("linear_layers")
    if not layers:
        return None
    sequences = run["global_batch"]["sparse"] // run["cell"]["chips"]
    return layers * sequences * run["config"]["arch"]["sequence_length"]


def _passes(run: dict, forward, backward):
    """`forward` for each forward pass of a step (two where the trace holds
    recomputed operations) and `backward` for the backward pass."""
    return (2 if scope_tree.pass_ms(run, "recomputed") else 1) * forward \
        + backward


def _widths(config: dict):
    """(key width, value width, value heads, a state's entries)."""
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    hv = config["linear_num_value_heads"]
    return config["linear_num_key_heads"] * dk, hv * dv, hv, dk * dv


def rule_flops_per_step(run: dict) -> Optional[int]:
    tokens = _tokens(run)
    if tokens is None:
        return None
    _, _, heads, state = _widths(run["config"])
    forward = RULE_PRODUCTS * 2 * state * heads
    return tokens * _passes(run, forward, 2 * forward)


def rule_bytes_per_step(run: dict) -> Optional[int]:
    tokens = _tokens(run)
    if tokens is None:
        return None
    keys, values, heads, _ = _widths(run["config"])
    read = 2 * (2 * keys + values) + 2 * 4 * heads      # q k v | g beta
    forward = read + 2 * values                         # o
    backward = read + 2 * values + read                 # do | five cotangents
    return tokens * _passes(run, forward, backward)


def rule_roofline_share(run: dict) -> Optional[float]:
    """The least time the rule could take a step (the larger of the
    recurrence's operations over the bf16 peak and its bytes over HBM
    bandwidth) over `gdn_rule`'s device time, in per cent."""
    ms = scopes_ms(run, ("gdn_rule",))
    flops = rule_flops_per_step(run)
    if not ms or flops is None:
        return None
    least = max(flops / run["peaks"]["bf16_flops_per_s"],
                rule_bytes_per_step(run) / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1e3)


def _pass_share(run: dict, scope: str, channels_of, costs
                ) -> Optional[float]:
    """The least time a pass over `[tokens, channels_of(key width, value
    width)]` at `costs` bytes an entry could take (over HBM bandwidth) over
    the scope's device time, in per cent."""
    ms = scopes_ms(run, (scope,))
    tokens = _tokens(run)
    if not ms or tokens is None:
        return None
    keys, values, _, _ = _widths(run["config"])
    need = tokens * channels_of(keys, values) * _passes(run, *costs)
    return 100.0 * (need / run["peaks"]["hbm_bytes_per_s"]) / (ms / 1e3)


def conv_roofline_share(run: dict) -> Optional[float]:
    """`gdn_conv` over the channels of `[q | k | v]`."""
    return _pass_share(run, "gdn_conv",
                       lambda keys, values: 2 * keys + values, CONV_BYTES)


def norm_gate_roofline_share(run: dict) -> Optional[float]:
    """`gdn_norm_gate` over the value heads' channels."""
    return _pass_share(run, "gdn_norm_gate", lambda keys, values: values,
                       NORM_GATE_BYTES)
