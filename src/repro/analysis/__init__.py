"""Static-analysis + sanitizer subsystem: the standing contracts as rules.

Three layers (see ``analysis/README.md`` for the rule catalogue):

* runtime sanitizer — ``analysis.sanitize(strict=True)`` wires
  ``jax.transfer_guard`` around engine decode regions and diffs the
  retrace registry across steady-state regions;
* donation checker — compiled-HLO ``input_output_alias`` verification
  for every ``register_jit(donated=...)`` launch, plus the debug-mode
  stale-buffer poisoner;
* AST lint — ``python -m repro.analysis.lint src/repro`` (rules
  MG101–MG107, stdlib-only, blocking in CI).

Beside them, ``spans``: ``moegen.*`` host spans on the profiler's clock,
switched on by ``tracing()`` (every planned transfer scope is one).
"""
from repro.analysis.donation import DonationCheck, check_donation
from repro.analysis.markers import hot_path, is_hot_path
from repro.analysis.registry import TraceKeySet, register_jit
from repro.analysis.runtime import (
    DonationViolation,
    RetraceViolation,
    Sanitizer,
    SanitizerError,
    allowed,
    decode_region,
    poison_stale,
    sanitize,
)
from repro.analysis.spans import span, tracing

__all__ = [
    "DonationCheck",
    "DonationViolation",
    "RetraceViolation",
    "Sanitizer",
    "SanitizerError",
    "TraceKeySet",
    "allowed",
    "check_donation",
    "decode_region",
    "hot_path",
    "is_hot_path",
    "poison_stale",
    "register_jit",
    "sanitize",
    "span",
    "tracing",
]
