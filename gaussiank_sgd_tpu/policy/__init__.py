"""Telemetry-driven adaptive policy engine (docs/ADAPTIVE.md).

Closes the loop from the event bus to the training knobs: rolling
signals (:mod:`.signals`) feed rule-based policies (:mod:`.rules`) whose
decisions the :class:`~.engine.PolicyEngine` releases — with hysteresis,
cooldown, a decision budget, and probation/quarantine — for the Trainer
to apply at recompile-safe boundaries.
"""

from .engine import PolicyEngine
from .rules import (
    KNOB_BUCKET,
    KNOB_COMPRESSOR,
    KNOB_DENSITY,
    KNOB_OVERLAP,
    KNOB_WIRE,
    KNOBS,
    DensityRule,
    ExchangePromotionRule,
    OverlapPromotionRule,
    PolicyDecision,
    Rule,
    RuleContext,
    SelectorRule,
    default_rules,
)
from .signals import PolicySignals, SignalSnapshot

__all__ = [
    "PolicyEngine",
    "PolicyDecision",
    "PolicySignals",
    "SignalSnapshot",
    "Rule",
    "RuleContext",
    "SelectorRule",
    "DensityRule",
    "ExchangePromotionRule",
    "OverlapPromotionRule",
    "default_rules",
    "KNOBS",
    "KNOB_COMPRESSOR",
    "KNOB_DENSITY",
    "KNOB_WIRE",
    "KNOB_BUCKET",
    "KNOB_OVERLAP",
]
