"""Layer: exchange. Device time of the cross-chip collective operations of
the sparse step program (by XLA's opcode: all-reduce, all-gather,
reduce-scatter, all-to-all, collective-permute) per step of the profiled
sparse block, averaged over the chips: each worker's k selected (index,
value) pairs gathered from every peer, which XLA serves as two all-reduces
of P x k on this chip, and the scalar all-reduce of the guard and the
step's metrics. A collective's time includes its wait for the slowest peer.
What it replaces, the dense baseline's all-reduce of the whole float32
gradient, is printed by a traced run (`trace dense: ... collectives per
step`) and is no metric: the dense rate that it moves is not reported end
to end in the four-chip cell (PERF.md sections 2 and 7).

By opcode and not by the program's scope `exchange`: in the compiled
programs no operation carries that scope (XLA's all-reduce combiner merges
the gradient's all-reduce with the metrics' and keeps the latter's name:
the dense program's 1.05 ms all-reduce reads under `step_metrics`; my chip
runs, PR 26). Nothing to read on one chip: the collectives over a
one-device axis are gone from the compiled program. On two chips or more
a program without a collective reads 0: there the exchange is gone, which
a reader that returned nothing would hide. Moves `examples_per_s`, in a
cell where the exchange is a share of the step that its bound can see
(at `vgg16_dp4`'s 5120 a worker it is not: PERF.md section 4). Source:
device_trace."""


def read(run):
    t = run.get("trace")
    if not t or "sparse" not in t["arms"] or t["arms"]["sparse"]["chips"] < 2:
        return None
    return 1e3 * t["arms"]["sparse"]["collective_s_per_step"]
