"""Rule: collective-outside-pipeline.

The bucket-pipelined step (ISSUE 7, parallel/trainstep.py) only hides
exchange latency when every payload collective is issued through one of
the sanctioned funnels — ``_gather`` / ``_pipeline_launch`` inside the
step builder, or ``butterfly_rounds`` in parallel/gtopk.py. A raw
``lax.all_gather`` / ``lax.ppermute`` added elsewhere in ``parallel/``
silently bypasses three invariants at once: the eligibility gate (the
collective runs sequentially even when the build says "pipelined"), the
``exchange`` scope (a device trace no longer books it as exchange time),
and the overlapped-bytes accounting. This rule flags payload collectives
in ``parallel/`` whose enclosing-function chain contains no sanctioned
funnel name;
deliberately sequential call sites (parallel/collectives.py's reference
implementations) carry an inline suppression with their justification.

``ring_attention`` is sanctioned too: its K/V-rotation ppermute is model
compute inside its own scan pipeline, not a gradient-exchange payload.
"""

from __future__ import annotations

import ast
import os
from typing import Iterator

from ..core import Finding, ModuleCtx

NAME = "collective-outside-pipeline"
SEVERITY = "error"

#: payload collectives the pipelined schedule must own
_PAYLOAD_COLLECTIVES = {"all_gather", "ppermute"}

#: enclosing-def names through which payload collectives may be issued
_SANCTIONED_FUNNELS = {"_gather", "_pipeline_launch", "butterfly_rounds",
                       "ring_attention"}


def _terminal_name(func: ast.AST) -> str:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return ""


class Rule:
    name = NAME
    severity = SEVERITY
    description = ("lax.all_gather/lax.ppermute in parallel/ must be "
                   "issued through a sanctioned pipeline funnel "
                   "(_gather, _pipeline_launch, butterfly_rounds)")

    def check(self, ctx: ModuleCtx) -> Iterator[Finding]:
        if os.path.basename(os.path.dirname(ctx.path)) != "parallel":
            return
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call)
                    and _terminal_name(node.func) in _PAYLOAD_COLLECTIVES):
                continue
            chain = [a.name for a in ctx.ancestors(node)
                     if isinstance(a, (ast.FunctionDef,
                                       ast.AsyncFunctionDef))]
            if any(name in _SANCTIONED_FUNNELS for name in chain):
                continue
            fname = _terminal_name(node.func)
            yield Finding(
                rule=self.name, severity=self.severity, path=ctx.path,
                line=node.lineno, col=node.col_offset,
                end_line=getattr(node, "end_lineno", 0) or 0,
                message=(f"payload collective {fname}() issued outside "
                         f"the sanctioned pipeline funnels "
                         f"({', '.join(sorted(_SANCTIONED_FUNNELS))}): "
                         f"it bypasses the overlap eligibility gate, the "
                         f"exchange scope, and the overlapped-bytes "
                         f"accounting (parallel/trainstep.py)"),
                source_line=ctx.src(node))
