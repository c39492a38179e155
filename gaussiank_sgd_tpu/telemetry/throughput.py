"""Rolling-window throughput / MFU tracker — skipped-step aware.

The naive images/sec (global_batch / step_s) the reference logs LIES under
the resilience runtime: a step the non-finite guard turned into a no-op
took wall-clock time but trained on nothing, and a rollback rewinds the
model so the window straddling it mixes two trajectories. This tracker
owns both corrections:

* a skipped step contributes its SECONDS but zero EXAMPLES (the time was
  really spent; the work was discarded) — so throughput degrades honestly
  under skips instead of reporting phantom images/sec;
* :meth:`reset` empties the window — the trainer calls it on rollback so
  post-restore throughput is measured on the new trajectory only.

MFU uses the same convention: only useful (unskipped) steps count model
FLOPs, against the chip's peak (:data:`PEAK_FLOPS_BY_KIND`); the step's
FLOPs come from XLA's cost analysis of the program that ran
(:func:`program_flops`).

The tracker is thread-safe, and :meth:`signals` returns the one canonical
:class:`ThroughputSignals` snapshot both the trainer's log line and the
adaptive policy engine read — consumers never poke at private fields, and
every number in one snapshot comes from the same instant under the lock.
"""

from __future__ import annotations

import threading
from collections import deque
from dataclasses import dataclass
from typing import Optional

# Dense bf16 peak FLOP/s per chip, keyed by the EXACT jax ``device_kind``
# (spellings as in jax's own pallas/mosaic/tpu_info.py). Source of every
# figure: the Google Cloud TPU documentation page of that generation.
# MFU here = model FLOPs / (step time * peak).
PEAK_FLOPS_BY_KIND = {
    "TPU v4": 275e12,        # "TPU v4"
    "TPU v5 lite": 197e12,   # "TPU v5e"
    "TPU v5": 459e12,        # "TPU v5p"
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,   # "TPU v6e" (Trillium)
}


def device_peak_flops(device) -> Optional[float]:
    """bf16 peak FLOP/s of the chip ``device`` (a ``jax.Device``). None
    off-TPU (no MFU on CPU — the field is absent, "not measured"); a TPU
    whose ``device_kind`` is not in the table raises — a missing peak is
    an error, never another generation's figure."""
    if device.platform != "tpu":
        return None
    if device.device_kind not in PEAK_FLOPS_BY_KIND:
        raise KeyError(
            f"no peak FLOP/s on record for TPU device_kind "
            f"{device.device_kind!r}; add it to PEAK_FLOPS_BY_KIND "
            f"(telemetry/throughput.py) with its source")
    return PEAK_FLOPS_BY_KIND[device.device_kind]


def program_flops(jitted, *args) -> Optional[float]:
    """FLOP count of a jitted program from XLA's HLO cost analysis.

    An *analytic* count computed from HLO op shapes (conv/matmul terms
    dominate), not a measurement, and exact for the program actually
    compiled. Lowers and compiles ``jitted`` for ``args`` — a cache hit
    when that program already ran, a full compile when it has not. Errors
    propagate; None only when the backend's analysis reports no FLOPs.
    """
    flops = jitted.lower(*args).compile().cost_analysis().get("flops", 0.0)
    return float(flops) if flops else None


def mfu(flops_per_step: Optional[float], step_seconds: float,
        peak: Optional[float]) -> Optional[float]:
    """Model-FLOPs utilization; None when FLOPs or peak are unavailable."""
    if not flops_per_step or not peak or step_seconds <= 0:
        return None
    return flops_per_step / (step_seconds * peak)


@dataclass(frozen=True)
class ThroughputSignals:
    """One consistent read of the tracker (all fields from the same
    instant). ``step_s_ema`` is the EMA of per-step wall-clock seconds
    (skipped steps included — their time was really spent); ``mfu`` is
    None unless FLOPs/peak were passed to :meth:`ThroughputTracker.
    signals`."""

    window_steps: int = 0
    skipped_in_window: int = 0
    total_seconds: float = 0.0
    step_s_ema: Optional[float] = None
    examples_per_s: Optional[float] = None
    steps_per_s: Optional[float] = None
    mfu: Optional[float] = None


class ThroughputTracker:
    """Rolling window of (examples, seconds, skipped) step samples."""

    def __init__(self, window: int = 50, ema_beta: float = 0.9):
        if window <= 0:
            raise ValueError(f"window must be positive, got {window}")
        if not 0.0 < ema_beta < 1.0:
            raise ValueError(f"ema_beta must be in (0, 1), got {ema_beta}")
        self.window = window
        self._lock = threading.Lock()
        self._samples: deque = deque(maxlen=window)
        self._beta = float(ema_beta)
        self._step_ema: Optional[float] = None

    def update(self, examples: float, seconds: float,
               skipped: bool = False) -> None:
        """Record one step. ``examples`` is the step's GLOBAL batch;
        ``seconds`` its wall-clock (device + dispatch) time."""
        if seconds < 0:
            raise ValueError(f"negative step time {seconds}")
        with self._lock:
            self._samples.append(
                (0.0 if skipped else float(examples), float(seconds),
                 bool(skipped)))
            self._step_ema = (float(seconds) if self._step_ema is None
                              else self._beta * self._step_ema
                              + (1.0 - self._beta) * float(seconds))

    def reset(self) -> None:
        """Forget the window AND the EMA (trainer: on rollback — the
        restored trajectory must not average against the diverged one)."""
        with self._lock:
            self._samples.clear()
            self._step_ema = None

    def __len__(self) -> int:
        with self._lock:
            return len(self._samples)

    # -- *_locked internals (callers hold self._lock) ---------------------
    def _total_seconds_locked(self) -> float:
        return sum(s for _, s, _ in self._samples)

    def _examples_per_s_locked(self) -> Optional[float]:
        secs = self._total_seconds_locked()
        if not self._samples or secs <= 0:
            return None
        return sum(e for e, _, _ in self._samples) / secs

    def _steps_per_s_locked(self) -> Optional[float]:
        secs = self._total_seconds_locked()
        if not self._samples or secs <= 0:
            return None
        useful = sum(1 for _, _, sk in self._samples if not sk)
        return useful / secs

    @staticmethod
    def _mfu(sps: Optional[float], flops_per_step: Optional[float],
             peak_flops: Optional[float]) -> Optional[float]:
        if not flops_per_step or not peak_flops or sps is None:
            return None
        return flops_per_step * sps / peak_flops

    # -- public reads -----------------------------------------------------
    @property
    def total_seconds(self) -> float:
        with self._lock:
            return self._total_seconds_locked()

    @property
    def skipped_in_window(self) -> int:
        with self._lock:
            return sum(1 for _, _, sk in self._samples if sk)

    @property
    def examples_per_s(self) -> Optional[float]:
        """Useful examples per wall-clock second over the window; None
        until a sample with nonzero time exists."""
        with self._lock:
            return self._examples_per_s_locked()

    @property
    def steps_per_s(self) -> Optional[float]:
        """UNSKIPPED steps per second (skips burn time, produce nothing)."""
        with self._lock:
            return self._steps_per_s_locked()

    @property
    def step_s_ema(self) -> Optional[float]:
        """EMA of per-step wall-clock seconds (skips included)."""
        with self._lock:
            return self._step_ema

    def mfu(self, flops_per_step: Optional[float],
            peak_flops: Optional[float]) -> Optional[float]:
        """Model-FLOPs utilization over the window: useful-step FLOPs /
        (elapsed * peak). None when FLOPs/peak are unknown (CPU) or the
        window is empty."""
        with self._lock:
            return self._mfu(self._steps_per_s_locked(), flops_per_step,
                             peak_flops)

    def signals(self, flops_per_step: Optional[float] = None,
                peak_flops: Optional[float] = None) -> ThroughputSignals:
        """The canonical snapshot (see module docstring): every field is
        read under one lock acquisition, so the policy engine and the
        report CLI see the same numbers a log line was stamped from."""
        with self._lock:
            sps = self._steps_per_s_locked()
            return ThroughputSignals(
                window_steps=len(self._samples),
                skipped_in_window=sum(
                    1 for _, _, sk in self._samples if sk),
                total_seconds=self._total_seconds_locked(),
                step_s_ema=self._step_ema,
                examples_per_s=self._examples_per_s_locked(),
                steps_per_s=sps,
                mfu=self._mfu(sps, flops_per_step, peak_flops),
            )
