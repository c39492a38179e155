"""Operations and bytes of the splash-attention kernels that
`models/mellum2.py` calls (PR 31), counted from the masks: what the
algorithm needs and no more, so a share of a roofline cannot read high.

A (query, key) pair that the mask lets through costs, per query head and
head size d, 2 d operations for each product it takes part in. The kernels'
products (`jax.experimental.pallas.ops.tpu.splash_attention`):

  splash_mqa_fwd_residuals      q k^T, p v                      2 products
  splash_mqa_dq_no_residuals    q k^T, do v^T, ds k             3
  splash_mqa_dkv_no_residuals   q k^T, do v^T, p^T do, ds^T q   4

Pairs of blocks that the mask leaves partly empty are computed whole by the
kernel and counted here by their pairs alone. The bytes are each operand
and result once (bfloat16 q, k, v, o and their cotangents, float32 row
statistics broadcast over 128 lanes as the kernel keeps them)."""

from __future__ import annotations

from benchmarks import model_scopes

PRODUCTS = {"splash_mqa_fwd_residuals": 2, "splash_mqa_dq_no_residuals": 3,
            "splash_mqa_dkv_no_residuals": 4}
# arrays of [query heads, S, d] and of [key/value heads, S, d] that a call
# reads or writes, and float32 [query heads, S, 128] row statistics
ARRAYS = {"splash_mqa_fwd_residuals": (2, 2, 1),        # q o | k v | lse
          "splash_mqa_dq_no_residuals": (4, 2, 2),      # q o do dq | k v
          "splash_mqa_dkv_no_residuals": (3, 4, 2)}     # q o do | k v dk dv


def pairs(positions: int, window=None) -> int:
    """(query, key) pairs with `0 <= i - j` and, with a window, `i - j <
    window`, in one sequence."""
    if window is None or window >= positions:
        return positions * (positions + 1) // 2
    return window * (window + 1) // 2 + (positions - window) * window


def layer_pairs(config: dict) -> list:
    """Pairs a sequence of each of the configuration's layers."""
    s = config["arch"]["sequence_length"]
    return [pairs(s, config["sliding_window"]
                  if kind == "sliding_attention" else None)
            for kind in config["layer_types"][:config["num_hidden_layers"]]]


def flops_per_pass(config: dict, kernel: str, sequences: int) -> int:
    """One call of the kernel for every layer, over `sequences`."""
    per_pair = (PRODUCTS[kernel] * 2 * config["head_dim"]
                * config["num_attention_heads"])
    return per_pair * sum(layer_pairs(config)) * sequences


def bytes_per_pass(config: dict, kernel: str, sequences: int) -> int:
    s, d = config["arch"]["sequence_length"], config["head_dim"]
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    q_like, kv_like, stats = ARRAYS[kernel]
    per_layer = (2 * s * d * (q_like * hq + kv_like * hkv)
                 + 4 * s * 128 * stats * hq)
    return per_layer * config["num_hidden_layers"] * sequences


def roofline_share(run: dict, kernel: str):
    """The least time the kernel's calls of one step could take (the larger
    of operations over the bf16 peak and bytes over HBM bandwidth) over
    their device time, in per cent; None where the trace has no such
    kernel."""
    k = model_scopes.kernel(run, kernel)
    if not k or not k["s_per_step"]:
        return None
    config = run["config"]
    layers = config["num_hidden_layers"]
    # calls a step: one a layer and pass (the forward kernel runs again
    # where a layer is recomputed in its backward pass)
    passes = k["calls_per_step"] / layers
    sequences = run["global_batch"]["sparse"] // run["cell"]["chips"]
    least = max(
        flops_per_pass(config, kernel, sequences)
        / run["peaks"]["bf16_flops_per_s"],
        bytes_per_pass(config, kernel, sequences)
        / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * passes * least / k["s_per_step"]
