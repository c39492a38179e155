"""Layer: expert kernels. `ragged_dot_roofline` for a model whose layers do
not all have experts: the grouped-product kernel `ragged-dot-none`, the
least time its calls of one step could take over their device time, with
the calls divided and the experts' weights multiplied by the configuration's
`arch.expert_layers` (`moe_ops.py` takes `num_hidden_layers` for both, which
here counts a layer without experts). Operations: 2 a multiply-add of the
rows the held experts really got (the counter `moe_held_assignments`);
bytes: the rows in and out in bfloat16 and the held experts' matrices once a
pass. Where an expert gets few rows the experts' bytes bound the kernel and
not its operations (8 experts of 3 x 2048 x 768 a layer and about 512 rows
each: 0.79 ms of operations and 0.71 ms of bytes a pass, near the ridge;
fewer rows, and the bytes are the larger): the reader takes the larger of
the two, whichever it is. Cannot pass 100%. None where the trace has no
such kernel, the program no such counter or the configuration no
`arch.expert_layers`. Moves `examples_per_s`. Source: device_trace."""

from benchmarks import model_scopes, moe_ops


def read(run):
    config = run["config"]
    layers = config.get("arch", {}).get("expert_layers")
    k = model_scopes.kernel(run, moe_ops.KERNEL)
    held = model_scopes.counter(run, "moe_held_assignments")
    if not layers or not k or not k["s_per_step"] or held is None:
        return None
    passes = k["calls_per_step"] / (moe_ops.CALLS_PER_LAYER_AND_PASS * layers)
    rows = held * moe_ops.CALLS_PER_LAYER_AND_PASS * (
        config["hidden_size"] + config["moe_intermediate_size"])
    weights = (layers * config["n_routed_experts"]
               * config["arch"]["expert_product_macs_per_assignment"])
    least = max(
        moe_ops.flops_per_pass(config, held) / run["peaks"]["bf16_flops_per_s"],
        2.0 * (rows + weights) / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * passes * least / k["s_per_step"]
