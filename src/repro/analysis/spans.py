"""Program spans on the profiler's clock.

``span(name, **args)`` marks one piece of host work as a
``jax.profiler.TraceAnnotation`` named ``moegen.<name>``; its ``args``
become the event's stats (ints, floats and strings as given, anything
else as its ``str``).  Device events and these spans share the
profiler's clock, so a reader of the trace can say which host work was
running while the device sat idle.

Spans sit at layer boundaries only, never per slot or per token:

* ``step``, ``admit``, ``prefill``, ``decode``, ``emit`` — the scheduler
  (``serving/server.py``);
* ``engine.layer`` — each layer of a decode or prefill pass
  (``core/engine.py``);
* ``sample`` — ``BatchSampler.sample``;
* ``xfer`` — every planned transfer scope (``runtime.allowed``), tagged
  with the scope's tag, plus the window key of a ``StreamWindow`` copy;
* ``stream.wait`` — the host blocked in ``StreamWindow.acquire`` until a
  copy lands, with its tag, key, bytes and whether it was fetched on
  demand.

Tracing is off unless a ``tracing()`` block is open: then ``span``
returns one shared null context after a single bool test, and builds no
name, no stats and no annotation.
"""
from __future__ import annotations

import contextlib

import jax

PREFIX = "moegen."

_ON = False
_OFF = contextlib.nullcontext()


def span(name: str, **args):
    """The host span ``moegen.<name>`` with ``args`` as its stats, or a
    null context when tracing is off."""
    if not _ON:
        return _OFF
    return jax.profiler.TraceAnnotation(
        PREFIX + name,
        **{k: v if isinstance(v, (int, float, str)) else str(v)
           for k, v in args.items()})


@contextlib.contextmanager
def tracing(on: bool = True):
    """Switch program spans on (or off) for the body."""
    global _ON
    prev, _ON = _ON, on
    try:
        yield
    finally:
        _ON = prev
