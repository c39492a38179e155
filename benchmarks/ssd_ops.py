"""Operations and bytes of the Mamba-2 state-space mixer
(`models/blocks/ssm.Mamba2Mixer`, scope `ssm`; PR 48), and the readings of
its scopes. Counted from shapes and from the RECURRENCE, not from what
implements it: what the algorithm needs and no more, so a share of a roofline
cannot read high.

The mixer's scopes: `ssm_in_proj` (one product to `[z | x B C | dt]`),
`ssm_conv` (a depthwise causal convolution of a few taps with a bias and SiLU
over `x B C`), `ssm_scan` (the softplus of `dt` and the scan), `ssm_norm_gate`
(`y silu(z)` under an RMSNorm over each group's columns), `ssm_out_proj`;
`ssm` around them holds what lies directly under it.

The scan, a token and head, with a state of `N x P` (`ssm_state_size` x
`mamba_head_dim`): the write `dt B x^T` and the read `S^T C`, two products
of `N x P`, 2 operations a multiply-add; twice that backward; the forward
again where the block is recomputed in its backward pass (`nn.remat`;
whether it is, the trace says: operations under `rematted_computation`). The
chunked form that runs on the chip does more (a chunk's `C B^T` and its
`[chunk, chunk]` block of decays) and none of the more is counted: a later
kernel is then read on the same work. Its bytes a token: `x` (bfloat16), `B`
and `C` (a group's, bfloat16) and `dt` (float32) read and `y` written
(bfloat16) forward; the same read again, `y`'s cotangent read and four
cotangents written backward. The state never crosses memory in this count.

`ssm_conv` and `ssm_norm_gate` are passes over `[tokens, channels]` bound by
memory on any chip, counted as `gdn_ops.py` counts the delta rule's, by ITS
bytes an entry and its count of passes (imported, not repeated):

  ssm_conv       forward  reads x, writes y (bfloat16)           4 bytes
                 backward reads dy, x, writes dx                  6 bytes
  ssm_norm_gate  forward  reads y, z, writes the product          6 bytes
                 backward reads the cotangent, y, z, writes dy, dz  10 bytes

Where the trace names none of the scopes (a parent commit, another model's
cell) or the configuration no `arch.ssm_layers`, every function returns
None, and nothing raises.
"""

from __future__ import annotations

from typing import Optional

from benchmarks.gdn_ops import (CONV_BYTES, NORM_GATE_BYTES, _passes,
                                scopes_ms)

SCOPES = ("ssm", "ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_norm_gate",
          "ssm_out_proj")
SCAN_PRODUCTS = 2               # the write, S^T C


def _tokens(run: dict) -> Optional[int]:
    """Tokens that one chip's state-space blocks go over a step, all
    blocks."""
    layers = run["config"].get("arch", {}).get("ssm_layers")
    if not layers:
        return None
    sequences = run["global_batch"]["sparse"] // run["cell"]["chips"]
    return layers * sequences * run["config"]["arch"]["sequence_length"]


def _widths(config: dict):
    """(the heads' columns, a group's B and C columns together, heads, a
    state's entries)."""
    heads, size = config["mamba_num_heads"], config["mamba_head_dim"]
    state = config["ssm_state_size"]
    return heads * size, 2 * config["n_groups"] * state, heads, state * size


def scan_flops_per_step(run: dict) -> Optional[int]:
    tokens = _tokens(run)
    if tokens is None:
        return None
    _, _, heads, state = _widths(run["config"])
    forward = SCAN_PRODUCTS * 2 * state * heads
    return tokens * _passes(run, forward, 2 * forward)


def scan_bytes_per_step(run: dict) -> Optional[int]:
    tokens = _tokens(run)
    if tokens is None:
        return None
    inner, both, heads, _ = _widths(run["config"])
    read = 2 * (inner + both) + 4 * heads               # x B C | dt
    forward = read + 2 * inner                          # y
    backward = read + 2 * inner + read                  # dy | four cotangents
    return tokens * _passes(run, forward, backward)


def scan_roofline_share(run: dict) -> Optional[float]:
    """The least time the scan could take a step (the larger of the
    recurrence's operations over the bf16 peak and its bytes over HBM
    bandwidth) over `ssm_scan`'s device time, in per cent."""
    ms = scopes_ms(run, ("ssm_scan",))
    flops = scan_flops_per_step(run)
    if not ms or flops is None:
        return None
    least = max(flops / run["peaks"]["bf16_flops_per_s"],
                scan_bytes_per_step(run) / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (ms / 1e3)


def _pass_share(run: dict, scope: str, with_groups: bool, costs
                ) -> Optional[float]:
    """The least time a pass over `[tokens, the heads' columns]`
    (`with_groups`: and B's and C's) at `costs` bytes an entry could take
    (over HBM bandwidth) over the scope's device time, in per cent."""
    ms = scopes_ms(run, (scope,))
    tokens = _tokens(run)
    if not ms or tokens is None:
        return None
    inner, both, _, _ = _widths(run["config"])
    need = tokens * (inner + with_groups * both) * _passes(run, *costs)
    return 100.0 * (need / run["peaks"]["hbm_bytes_per_s"]) / (ms / 1e3)


def conv_roofline_share(run: dict) -> Optional[float]:
    """`ssm_conv` over the channels of `x B C`."""
    return _pass_share(run, "ssm_conv", True, CONV_BYTES)


def norm_gate_roofline_share(run: dict) -> Optional[float]:
    """`ssm_norm_gate` over the heads' channels."""
    return _pass_share(run, "ssm_norm_gate", False, NORM_GATE_BYTES)
