"""Layer: dense step program. Operations a training step needs, counted from
layer shapes (`flops.py`: 2 per multiply-add, forward and twice that
backward, nothing recomputed) over the time the device was busy per dense
step, the chips and the chip's bf16 peak. Cannot pass 100%. Moves
`dense_examples_per_s`. Source: device_trace."""

from benchmarks import flops


def read(run):
    t = run.get("trace")
    if not t or "dense" not in t["arms"]:
        return None
    busy = t["arms"]["dense"]["busy_s_per_step"]
    chips = run["cell"]["chips"]
    need = flops.train_flops_per_step(run["config"],
                                      run["global_batch"]["dense"])
    return 100.0 * need / (busy * chips * run["peaks"]["bf16_flops_per_s"])
