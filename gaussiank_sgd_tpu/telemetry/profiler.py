"""jax.profiler trace-session hooks, armable for a step range.

Replaces the trainer's inline start/stop bookkeeping: one object owns the
window state, emits ``profile`` events onto the bus (so the JSONL stream
records exactly which steps the trace covers — without that, correlating
a trace directory with run history is guesswork), and guarantees the
trace is stopped on close even when training exits early (an unstopped
trace corrupts the output directory).

jax imports live inside methods: the telemetry package stays importable
without initializing a backend (the report/validate CLI path).
"""

from __future__ import annotations

import logging
from typing import Optional

from .bus import EventBus


class ProfilerSession:
    """Arms ``jax.profiler`` for global steps [start_step, stop_step).

    Drive :meth:`maybe_transition` with the CURRENT global step once per
    train-loop iteration; the session starts the trace when the window is
    entered (also when entered late — a resumed run whose start step is
    already past still profiles the remainder) and stops it when the step
    reaches ``stop_step``.
    """

    def __init__(self, logdir: str, start_step: int, stop_step: int,
                 bus: Optional[EventBus] = None,
                 logger: Optional[logging.Logger] = None):
        if stop_step <= start_step:
            raise ValueError(
                f"profiler window is empty: start {start_step} >= "
                f"stop {stop_step}")
        if start_step < 0:
            raise ValueError(f"negative start_step {start_step}")
        self.logdir = logdir
        self.start_step = start_step
        self.stop_step = stop_step
        self._bus = bus
        self._logger = logger
        self.active = False
        self._done = False      # one window per session, never re-arm

    def _emit(self, action: str, step: int) -> None:
        if self._bus is not None:
            self._bus.emit("profile", action=action, step=step,
                           logdir=self.logdir)
        if self._logger is not None:
            self._logger.info("profiler %s at step %d -> %s", action, step,
                              self.logdir)

    def _start_due(self, step: int) -> bool:
        return (not self.active and not self._done
                and self.start_step <= step < self.stop_step)

    def _stop_due(self, step: int) -> bool:
        return self.active and step >= self.stop_step

    def transition_due(self, step: int) -> bool:
        """Whether :meth:`maybe_transition` would start or stop the trace
        at ``step``: a loop that keeps a step in flight lets none run
        across the window's edge."""
        return self._start_due(step) or self._stop_due(step)

    def maybe_transition(self, step: int) -> None:
        """Start/stop the trace according to the armed window."""
        import jax

        if self._start_due(step):
            jax.profiler.start_trace(self.logdir)
            self.active = True
            self._emit("start", step)
        elif self._stop_due(step):
            jax.profiler.stop_trace()
            self.active = False
            self._done = True
            self._emit("stop", step)

    def close(self) -> None:
        """Stop a still-running trace (early exit / preemption)."""
        if self.active:
            import jax

            jax.profiler.stop_trace()
            self.active = False
            self._done = True
            self._emit("stop", self.stop_step)
