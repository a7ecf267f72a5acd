"""Runtime sanitizer: transfer-guarded decode regions, steady-state
retrace detection, donation verification hooks, stale-buffer poisoning.

The throughput thesis rests on invariants that only *hold at runtime*:
steady-state decode must not transfer host<->device outside the planned
``StreamWindow``/readback points, must not retrace, and must really alias
its donated buffers.  This module turns them into enforced guards:

* ``sanitize(strict=True)`` — context manager activating a sanitizer.
  While active, every engine ``decode_region()`` executes under
  ``jax.transfer_guard("disallow")`` (``strict=False`` logs instead), so
  any IMPLICIT transfer — a numpy array or Python scalar silently fed
  into device math mid-tick — raises at the offending line.  Planned
  transfers (StreamWindow fetches, sampler-state uploads, token
  readbacks, the per-tick position vector) run inside ``allowed(tag)``
  scopes, which re-enter ``transfer_guard("allow")`` and count per-tag
  occurrences into the report.

* ``Sanitizer.steady()`` — marks a steady-state region: registry compile
  counts are snapshotted at entry and diffed at exit; in strict mode any
  growth raises ``RetraceViolation`` naming the retraced functions.

* donation checks — when ``sanitize(donation=True)`` is active, the first
  launch of every ``register_jit(donated=...)`` function is verified by
  ``repro.analysis.donation.check_donation`` (compiled-HLO
  ``input_output_alias`` inspection); a dropped donation raises
  ``DonationViolation`` in strict mode.

* ``poison_stale`` — debug mode (``sanitize(poison=True)``): after a
  donating launch the engine passes its pre-launch buffer leaves here and
  any leaf XLA did NOT consume is deleted, so a retained reference into
  ``engine.cache``/``pool_k``/``pool_v`` fails loudly
  ("Array has been deleted") instead of reading stale garbage.

Ambient activation for CI: ``REPRO_SANITIZE=strict|log`` arms a
process-wide sanitizer (no code changes needed — the tier-1 suite runs
under it); ``REPRO_SANITIZE_POISON=1`` adds poisoning;
``REPRO_SANITIZE_REPORT=<path>`` dumps the JSON report at interpreter
exit (uploaded as a CI artifact from the slow job).
"""
from __future__ import annotations

import atexit
import contextlib
import json
import os
from typing import Dict, List, Optional

import jax

from repro.analysis import registry, spans


class SanitizerError(AssertionError):
    """Base class for sanitizer contract violations."""


class RetraceViolation(SanitizerError):
    """A registered jitted function compiled during steady-state decode."""


class DonationViolation(SanitizerError):
    """A donated jitted function does not alias its donated inputs."""


class Sanitizer:
    def __init__(self, strict: bool = True, donation: bool = False,
                 poison: bool = False) -> None:
        self.strict = strict
        self.guard_mode = "disallow" if strict else "log"
        self.donation = donation
        self.poison = poison
        self.planned: Dict[str, int] = {}
        self.donation_checks: List[dict] = []
        self.steady_retraces: Dict[str, int] = {}
        self._checked: set = set()

    # -- steady-state retrace detection --------------------------------
    @contextlib.contextmanager
    def steady(self):
        """Steady-state region: no registered jit may compile inside it.

        Warm the traces first (run the identical workload once), then
        re-run under ``steady()`` — compile-count growth is a retrace."""
        base = registry.snapshot()
        yield
        grew = registry.growth(base)
        if grew:
            for name, delta in grew.items():
                self.steady_retraces[name] = (
                    self.steady_retraces.get(name, 0) + delta
                )
            if self.strict:
                raise RetraceViolation(
                    "steady-state retrace: compile count grew for "
                    + ", ".join(f"{n} (+{d})" for n, d in sorted(grew.items()))
                )

    # -- donation interception -----------------------------------------
    def check_donation_once(self, entry, args, kwargs) -> None:
        if entry.name in self._checked:
            return
        self._checked.add(entry.name)
        from repro.analysis import donation

        res = donation.check_donation(
            entry.fn, args, kwargs, entry.donated, name=entry.name
        )
        self.donation_checks.append(res.as_dict())
        if self.strict and not res.ok:
            raise DonationViolation(
                f"{entry.name}: donated inputs not aliased to outputs "
                f"({res.aliased}/{res.donated_leaves} leaves aliased"
                + (f"; {res.dropped[0]}" if res.dropped else "")
                + ")"
            )

    # -- reporting ------------------------------------------------------
    def report(self) -> dict:
        return {
            "mode": "strict" if self.strict else "log",
            "planned_transfers": dict(self.planned),
            "steady_retraces": dict(self.steady_retraces),
            "compile_counts": registry.compile_counts(),
            "trace_key_sets": registry.keyset_counts(),
            "donation_checks": list(self.donation_checks),
        }


# ---------------------------------------------------------------------------
# Active-sanitizer stack (+ ambient env activation)
# ---------------------------------------------------------------------------
_STACK: List[Sanitizer] = []
_AMBIENT: Optional[Sanitizer] = None
_AMBIENT_INIT = False


def _dump_report(san: Sanitizer, path: str) -> None:
    try:
        with open(path, "w") as f:
            json.dump(san.report(), f, indent=2, sort_keys=True)
    except OSError:
        pass


def _ambient() -> Optional[Sanitizer]:
    """Process-wide sanitizer armed from the environment (CI's strict
    flag).  Lazily constructed on first use so importing the package has
    no side effects."""
    global _AMBIENT, _AMBIENT_INIT
    if not _AMBIENT_INIT:
        _AMBIENT_INIT = True
        mode = os.environ.get("REPRO_SANITIZE", "").strip().lower()
        if mode in ("strict", "log", "1", "true"):
            _AMBIENT = Sanitizer(
                strict=mode != "log",
                poison=bool(os.environ.get("REPRO_SANITIZE_POISON")),
            )
            path = os.environ.get("REPRO_SANITIZE_REPORT")
            if path:
                atexit.register(_dump_report, _AMBIENT, path)
    return _AMBIENT


def current() -> Optional[Sanitizer]:
    """The innermost active sanitizer, or the ambient one, or None."""
    return _STACK[-1] if _STACK else _ambient()


@contextlib.contextmanager
def sanitize(strict: bool = True, donation: bool = False,
             poison: bool = False):
    """Activate a sanitizer for the body.  Yields the ``Sanitizer`` so
    callers can open ``steady()`` regions and read ``.report()`` after."""
    san = Sanitizer(strict=strict, donation=donation, poison=poison)
    _STACK.append(san)
    try:
        yield san
    finally:
        _STACK.pop()


# ---------------------------------------------------------------------------
# Region scopes (called from engine/serving hot paths)
# ---------------------------------------------------------------------------
@contextlib.contextmanager
def decode_region():
    """A decode/steady-state region: with a sanitizer active, implicit
    host<->device transfers are disallowed (strict) or logged inside."""
    san = current()
    if san is None:
        yield
        return
    with jax.transfer_guard(san.guard_mode):
        yield


@contextlib.contextmanager
def allowed(tag: str, **args):
    """A PLANNED transfer scope inside a decode region (StreamWindow
    ``device_put``s, sampler-state uploads, the per-tick position vector,
    token readback).  Re-enters ``transfer_guard("allow")`` and counts
    the occurrence under ``tag`` in the sanitizer report.  Whether or not
    a sanitizer is active, the scope is the program span ``xfer`` with
    ``tag`` and ``args`` as its stats (``repro.analysis.spans``)."""
    san = current()
    with spans.span("xfer", tag=tag, **args):
        if san is None:
            yield
            return
        san.planned[tag] = san.planned.get(tag, 0) + 1
        with jax.transfer_guard("allow"):
            yield


def on_donating_launch(entry, args, kwargs) -> None:
    """Registry hook: called before every launch of a donated-registered
    jit; verifies aliasing once per function when donation checking is
    active."""
    san = current()
    if san is None or not san.donation:
        return
    san.check_donation_once(entry, args, kwargs)


def poison_stale(old_leaves, current_tree) -> None:
    """Debug-mode stale-buffer poisoner.

    ``old_leaves``: the donated pytree's array leaves captured BEFORE the
    launch; ``current_tree``: the rebound buffers after it.  Any old leaf
    that is not part of the current buffers and was not consumed by
    donation is deleted, so retained references fail loudly.  No-op
    unless the active sanitizer has ``poison=True``."""
    san = current()
    if san is None or not san.poison or old_leaves is None:
        return
    live = {id(leaf) for leaf in jax.tree.leaves(current_tree)}
    for leaf in old_leaves:
        if (isinstance(leaf, jax.Array) and id(leaf) not in live
                and not leaf.is_deleted()):
            leaf.delete()
