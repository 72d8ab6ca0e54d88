"""Profiler hooks: phase scopes, host annotations, perfetto trace dumps.

Two complementary levels:

* :func:`phase` - ``jax.named_scope`` wrapper used *inside* jitted code
  (engine step phases, halo exchanges).  Zero runtime cost: it only names
  the HLO ops, so XLA profiles and dumped traces attribute time to
  ``repro.force`` / ``repro.halo.spin`` / ... instead of ``fusion.1234``.
* :func:`annotate` - ``jax.profiler.TraceAnnotation`` for *host-side*
  regions (chunk dispatch, checkpoint writes); shows up on the Python
  track of a profiler trace.

:func:`maybe_trace` wraps a run in ``jax.profiler`` start/stop when given
a dump directory (``Telemetry.profile_dir``), producing a
perfetto-loadable trace; with ``None`` it is a no-op.  A requested trace
that cannot start or stop raises: a run that asked for a trace never ends
as a success without one.
"""
from __future__ import annotations

import contextlib


def phase(name: str):
    """Trace-time scope naming a step phase inside jitted code."""
    import jax

    return jax.named_scope(f"repro.{name}")


def annotate(name: str):
    """Host-side profiler annotation (runtime region on the Python track)."""
    try:
        import jax.profiler

        return jax.profiler.TraceAnnotation(name)
    except Exception:           # profiler unavailable: degrade to no-op
        return contextlib.nullcontext()


@contextlib.contextmanager
def maybe_trace(profile_dir: str | None):
    """Dump a perfetto-loadable profiler trace to ``profile_dir`` (opt-in).

    Raises ``RuntimeError`` when the trace cannot start or stop."""
    if not profile_dir:
        yield
        return
    import jax.profiler

    try:
        jax.profiler.start_trace(str(profile_dir))
    except Exception as exc:
        raise RuntimeError(
            f"profiler trace could not start in {profile_dir}: {exc}") from exc
    try:
        yield
    finally:
        try:
            jax.profiler.stop_trace()
        except Exception as exc:
            raise RuntimeError(
                f"profiler trace could not stop in {profile_dir}: {exc}"
            ) from exc
