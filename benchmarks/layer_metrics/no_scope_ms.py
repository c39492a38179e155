"""Layer: sparse step program. Operations whose `op_name` carries none of the
step's scopes (`span_reduce`'s `scope_s_per_step[""]`): XLA's copies and
layout changes, `dynamic-update-slice`, the kernels the compiler emits without
an `op_name` (`ragged-dot-none`), the scalar programs of a log line; the run's
`scopes` line keeps them by opcode. With it `fwd_bwd_ms + flatten_ms +
ef_select_scope_ms + cand_topk_ms + pack_scatter_ms + update_ms + guard_ms +
step_metrics_ms + no_scope_ms` is the operations' self time per step. Every
cell lists this metric, so its reader is where every traced run has
`scope_tree`'s two lines printed. Moves `examples_per_s`. Source:
device_trace."""

from benchmarks import scope_tree, span_reduce


def read(run):
    scope_tree.reduced(run)
    return span_reduce.scope_ms(run, "")
