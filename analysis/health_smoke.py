"""CI smoke for the run-health gate (docs/OBSERVABILITY.md "Run health").

    JAX_PLATFORMS=cpu python analysis/health_smoke.py

Drives the full verdict path twice on the virtual mesh and gates on the
``telemetry health`` exit code — the same code a production CI job would
gate a run's stream with:

1. a clean mnistnet run with ``--health on`` must replay to exit 0 (ok),
   with every recorded verdict ok and the stream strictly valid;
2. the same run with a NaN batch injected (training/chaos.py) must
   rollback, replay to exit 2 (critical), and attribute the verdict to
   ``instability`` — proving the gate fails for the right reason, not
   just fails.

Exit codes: 0 both scenarios behave, 1 any expectation broke.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
from typing import List, Optional

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from gaussiank_sgd_tpu.telemetry.__main__ import (  # noqa: E402
    main as telemetry_cli)
from gaussiank_sgd_tpu.telemetry.events import validate_file   # noqa: E402

# trainer-side imports happen inside main(), AFTER virtual_cpu.provision
# — importing them first would initialize the single-device backend


def _cfg(outdir: str, **kw):
    from gaussiank_sgd_tpu.training.config import TrainConfig
    base = dict(
        dnn="mnistnet", dataset="mnist", batch_size=8, nworkers=8,
        lr=0.05, momentum=0.9, weight_decay=0.0, epochs=1, max_steps=10,
        compressor="gaussian", density=0.01, compress_warmup_steps=4,
        warmup_epochs=0.0, compute_dtype="float32", output_dir=outdir,
        log_every=2, eval_every_epochs=0, save_every_epochs=0, seed=0,
        health="on")
    base.update(kw)
    return TrainConfig(**base)


def _run(cfg, nan_steps=None) -> str:
    from gaussiank_sgd_tpu.training import chaos
    from gaussiank_sgd_tpu.training.trainer import Trainer
    t = Trainer(cfg)
    if nan_steps:
        chaos.inject_nan_batches(t, set(nan_steps))
    while t.step < t.total_steps:
        t.train(t.total_steps - t.step)
    t.close()
    return os.path.join(t.run_dir, "metrics.jsonl")


def main(argv: Optional[List[str]] = None) -> int:
    from gaussiank_sgd_tpu import compile_cache, virtual_cpu
    virtual_cpu.provision(8)
    compile_cache.enable_compile_cache()
    failures: List[str] = []
    with tempfile.TemporaryDirectory(prefix="health_smoke_") as tmp:
        # -- scenario 1: clean run gates green --------------------------
        clean = _run(_cfg(os.path.join(tmp, "clean")))
        rep = validate_file(clean, strict=True)
        if not rep.ok:
            failures.append(f"clean stream invalid: {rep.errors}")
        code = telemetry_cli(["health", clean])
        if code != 0:
            failures.append(f"clean run gated {code}, expected 0")

        # -- scenario 2: NaN chaos gates red, for the right reason ------
        chaotic = _run(_cfg(os.path.join(tmp, "chaos"), max_steps=12,
                            save_every_steps=4, max_consecutive_skips=1),
                       nan_steps={6})
        rep = validate_file(chaotic, strict=True)
        if not rep.ok:
            failures.append(f"chaos stream invalid: {rep.errors}")
        code = telemetry_cli(["health", chaotic])
        if code != 2:
            failures.append(f"chaos run gated {code}, expected 2")
        with open(chaotic, "r", encoding="utf-8") as fh:
            verdicts = [json.loads(line) for line in fh
                        if '"health_status"' in line]
        if not any("instability" in v.get("causes", ())
                   for v in verdicts):
            failures.append("chaos run never attributed 'instability'")

    for msg in failures:
        print(f"health smoke FAIL: {msg}", file=sys.stderr)
    if not failures:
        print("health smoke OK: clean run gates 0, NaN chaos gates 2 "
              "with cause 'instability'")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
