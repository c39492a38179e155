"""Logging & metrics.

Reference parity: ``settings.py``'s global logger (console + per-run file,
SURVEY.md §2 C10) and the log-line metrics its plot scripts parse
(SURVEY.md §5 "Metrics / logging"). Rebuilt per the survey's note as
structured JSONL — one record per logged step with loss/acc/step-time/
bytes-sent/density — alongside the human-readable lines.
"""

from __future__ import annotations

import logging
import os
import sys
from typing import Any, Dict, Optional

from ..telemetry.exporters import JSONLExporter


def make_logger(name: str = "gaussiank_sgd_tpu",
                log_file: Optional[str] = None,
                level: int = logging.INFO) -> logging.Logger:
    logger = logging.getLogger(name)
    logger.setLevel(level)
    logger.propagate = False
    if not logger.handlers:
        fmt = logging.Formatter(
            "%(asctime)s [%(levelname)s] %(message)s", "%H:%M:%S")
        sh = logging.StreamHandler(sys.stdout)
        sh.setFormatter(fmt)
        logger.addHandler(sh)
        if log_file:
            os.makedirs(os.path.dirname(log_file), exist_ok=True)
            fh = logging.FileHandler(log_file)
            fh.setFormatter(fmt)
            logger.addHandler(fh)
    return logger


class JSONLWriter(JSONLExporter):
    """Back-compat alias for :class:`telemetry.exporters.JSONLExporter`.

    The trainer now publishes through ``telemetry.EventBus`` (which stamps
    schema_version/seq/ts); this shim keeps the historical
    ``JSONLWriter(path).write(record)`` surface for external callers and
    old analysis scripts. Same thread-safety contract: the dump+write pair
    is serialized under a lock.
    """

    def write(self, record: Dict[str, Any]) -> None:
        self.emit(record)


class PhaseTimers:
    """Wall-clock phase means: io / step (fwd+bwd+comm fused under XLA).

    Reference parity: the io/fwd/bwd/comm breakdown in ``dl_trainer.py``
    (SURVEY.md §3.2, §5 Tracing). One jitted program owns fwd+bwd+comm here,
    so the honest breakdown is io vs device-step; finer slicing comes from
    ``jax.profiler`` traces (trainer.profile hooks), not host timers. The
    trainer times each phase itself: with one step in flight a step's
    phases are not one stretch of the clock (its dispatch, then the step
    before it, then its sync).
    """

    def __init__(self):
        self.sums: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}

    def add(self, phase: str, seconds: float) -> None:
        """One occurrence of ``phase``."""
        self.sums[phase] = self.sums.get(phase, 0.0) + seconds
        self.counts[phase] = self.counts.get(phase, 0) + 1

    def means(self) -> Dict[str, float]:
        return {k: self.sums[k] / max(1, self.counts[k]) for k in self.sums}

    def reset(self) -> None:
        self.sums.clear()
        self.counts.clear()
