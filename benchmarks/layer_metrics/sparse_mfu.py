"""Layer: sparse step program. The operations a training step needs
(`flops.py` over the configuration's `matmul_layers`: 2 per multiply-add,
forward and twice that backward; the experts' three products from the
counter `moe_held_assignments`, the rows they really got; nothing recomputed
counted) over the time the device was busy per sparse step, the chips and
the chip's bf16 peak. The one share of the whole step in a cell without a
dense arm. Cannot pass 100%. None where the program has no such counter.
Moves `examples_per_s`. Source: device_trace."""

from benchmarks import flops, model_scopes


def read(run):
    t = run.get("trace")
    held = model_scopes.counter(run, "moe_held_assignments")
    if not t or "sparse" not in t["arms"] or held is None:
        return None
    config, chips = run["config"], run["cell"]["chips"]
    # the counter is one worker's (the workers' mean), summed over layers
    workers = run["mix"]["nworkers"]
    experts = (3 * 2 * config["arch"]["expert_product_macs_per_assignment"]
               * held * workers)
    need = flops.train_flops_per_step(
        config, run["global_batch"]["sparse"]) + experts
    busy = t["arms"]["sparse"]["busy_s_per_step"]
    return 100.0 * need / (busy * chips * run["peaks"]["bf16_flops_per_s"])
