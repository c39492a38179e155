"""Operations and bytes of the splash-attention kernels that
`models/joyai_flash.py` calls for latent attention (PR 33): every query head
has a key/value head of its own (`make_splash_mha`), and queries and keys
are `qk_nope_head_dim + qk_rope_head_dim` wide (192) where values are
`v_head_dim` (128). Counted from the mask's pairs: what the algorithm needs
and no padding, so a share of a roofline cannot read high.

A (query, key) pair that the causal mask lets through costs, per head, 2
operations for every entry of the head size of each product it takes part
in. The kernels' products
(`jax.experimental.pallas.ops.tpu.splash_attention`):

  splash_mha_fwd_residuals      q k^T (192), p v (128)
  splash_mha_dq_no_residuals    q k^T (192), do v^T (128), ds k (192)
  splash_mha_dkv_no_residuals   q k^T (192), do v^T (128), p^T do (128),
                                ds^T q (192)

Pairs of blocks that the mask leaves partly empty are computed whole by the
kernel and counted here by their pairs alone. The bytes are each operand
and result once: bfloat16 arrays of [heads, S, 192] (q, k and their
cotangents) and of [heads, S, 128] (v, o, do, dv), float32 row statistics
broadcast over 128 lanes as the kernel keeps them (the log-sum-exp; in the
backward kernels also the rows' sum of do * o). Every kernel is bound by
operations at 8192 positions (about 4096 pairs a query against 5 or 6
arrays a position)."""

from __future__ import annotations

from benchmarks import model_scopes

# (products at the query/key head size, products at the value head size)
PRODUCTS = {"splash_mha_fwd_residuals": (1, 1),
            "splash_mha_dq_no_residuals": (2, 1),
            "splash_mha_dkv_no_residuals": (2, 2)}
# arrays of [heads, S, query/key size], of [heads, S, value size], and
# float32 [heads, S, 128] row statistics that a call reads or writes
ARRAYS = {"splash_mha_fwd_residuals": (2, 2, 1),       # q k | v o | lse
          "splash_mha_dq_no_residuals": (3, 2, 2),     # q k dq | v do
          "splash_mha_dkv_no_residuals": (3, 3, 2)}    # q k dk | v do dv


def pairs(positions: int) -> int:
    """(query, key) pairs with `0 <= i - j` in one sequence."""
    return positions * (positions + 1) // 2


def head_sizes(config: dict):
    return (config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
            config["v_head_dim"])


def flops_per_pass(config: dict, kernel: str, sequences: int) -> int:
    """One call of the kernel for every layer, over `sequences`."""
    dqk, dv = head_sizes(config)
    at_qk, at_v = PRODUCTS[kernel]
    per_pair = 2 * (at_qk * dqk + at_v * dv) * config["num_attention_heads"]
    return (per_pair * pairs(config["arch"]["sequence_length"])
            * config["arch"]["attention_layers"] * sequences)


def bytes_per_pass(config: dict, kernel: str, sequences: int) -> int:
    s, heads = config["arch"]["sequence_length"], config["num_attention_heads"]
    dqk, dv = head_sizes(config)
    at_qk, at_v, stats = ARRAYS[kernel]
    per_layer = heads * s * (2 * (at_qk * dqk + at_v * dv) + 4 * 128 * stats)
    return per_layer * config["arch"]["attention_layers"] * sequences


def roofline_share(run: dict, kernel: str):
    """The least time the kernel's calls of one step could take (the larger
    of operations over the bf16 peak and bytes over HBM bandwidth) over
    their device time, in per cent; None where the trace has no such kernel
    or the configuration no latent attention."""
    config = run["config"]
    k = model_scopes.kernel(run, kernel)
    if not k or not k["s_per_step"] or "attention_layers" not in config.get(
            "arch", {}):
        return None
    # calls a step: one a layer and pass, all of a worker's sequences in it
    # (the forward kernel runs again where a layer's recomputation does
    # not find its output kept)
    passes = k["calls_per_step"] / config["arch"]["attention_layers"]
    sequences = run["global_batch"]["sparse"] // run["cell"]["chips"]
    least = max(
        flops_per_pass(config, kernel, sequences)
        / run["peaks"]["bf16_flops_per_s"],
        bytes_per_pass(config, kernel, sequences)
        / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * passes * least / k["s_per_step"]
