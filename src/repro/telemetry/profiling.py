"""Profiler hooks: device phase scopes and host spans.

Two complementary levels, both landing in one ``jax.profiler`` trace on
one clock:

* :func:`phase` - ``jax.named_scope`` wrapper used *inside* jitted code
  (engine step phases, halo exchanges).  Zero runtime cost: it only names
  the HLO ops, so XLA profiles attribute time to ``repro.force`` /
  ``repro.halo.spin`` / ... instead of ``fusion.1234``.  A sub-phase is a
  dotted name nested under its phase (``repro.rebuild`` /
  ``repro.rebuild.search``), so the innermost ``repro.<phase>`` of an op
  stays the phase it belongs to.
* :func:`annotate` - ``jax.profiler.TraceAnnotation`` for *host-side*
  regions (``repro.run``, ``repro.chunk`` and its parts, checkpoint
  writes); shows up on the Python track of a profiler trace, so an idle
  gap of the device can be put down to what the host was doing.

Nothing here starts the profiler: an operator traces a window with
``jax.profiler.trace(dir)`` (or ``start_trace`` / ``stop_trace``) around
their own ``Engine.run`` calls, and these scopes and spans land in that
trace.
"""
from __future__ import annotations

import contextlib


def phase(name: str):
    """Trace-time scope naming a step phase inside jitted code."""
    import jax

    return jax.named_scope(f"repro.{name}")


def annotate(name: str, **metadata):
    """Host-side profiler annotation (runtime region on the Python track);
    ``metadata`` lands in the span's arguments.  Costs about a microsecond
    while no trace is open."""
    try:
        import jax.profiler

        return jax.profiler.TraceAnnotation(name, **metadata)
    except Exception:           # profiler unavailable: degrade to no-op
        return contextlib.nullcontext()
