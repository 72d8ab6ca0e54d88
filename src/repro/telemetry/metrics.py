"""Run metrics: counters/gauges registry + the compile watchdog.

:class:`RunMetrics` is a tiny in-process registry the Engine fills while a
run progresses - monotonic counters (steps, rebuilds, migrations, compile
events, halo bytes) and point-in-time gauges (steps/s, chunk-cache size,
peak device memory).  It is deliberately dependency-free: the runlog
(:mod:`repro.telemetry.runlog`) persists snapshots of it, and the report
renderer / future planner layers consume those.

:class:`CompileWatchdog` counts XLA backend compiles via
``jax.monitoring``.  JAX event listeners cannot be unregistered, so the
watchdog is a process-wide singleton and run-scoped accounting is done
with marks: ``mark()`` then ``since(mark)`` (the same delta pattern as
``launch/md_step._compile_counter``).  A steady-state run should show
``since(mark) == 0`` after its warmup chunk - the benchmarks gate on it
and ``tests/test_telemetry.py`` asserts it as a test.
"""
from __future__ import annotations

import dataclasses


# ---------------------------------------------------------------------------
# compile watchdog (process-wide singleton; delta reads are run-scoped)
# ---------------------------------------------------------------------------

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_WATCHDOG = {"count": 0, "seconds": 0.0, "registered": False}


def _ensure_listener() -> None:
    if _WATCHDOG["registered"]:
        return
    from jax import monitoring

    def _on_event(event: str, duration: float, **kw) -> None:
        if event == _COMPILE_EVENT:
            _WATCHDOG["count"] += 1
            _WATCHDOG["seconds"] += duration

    monitoring.register_event_duration_secs_listener(_on_event)
    _WATCHDOG["registered"] = True


class CompileWatchdog:
    """Process-wide XLA compile counter with run-scoped delta reads."""

    def __init__(self):
        _ensure_listener()

    @property
    def count(self) -> int:
        """Total backend compiles observed in this process so far."""
        return _WATCHDOG["count"]

    @property
    def seconds(self) -> float:
        """Total backend compile wall time [s] in this process so far."""
        return _WATCHDOG["seconds"]

    def mark(self) -> int:
        """Take a mark; pass it to :meth:`since` for a run-scoped delta."""
        return self.count

    def since(self, mark: int) -> int:
        return self.count - mark


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RunMetrics:
    """Counters (monotonic, ``inc``) and gauges (last value, ``set``)."""

    counters: dict = dataclasses.field(default_factory=dict)
    gauges: dict = dataclasses.field(default_factory=dict)

    def inc(self, name: str, value: float = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + value

    def set(self, name: str, value) -> None:
        self.gauges[name] = value

    def snapshot(self) -> dict:
        return {"counters": dict(self.counters), "gauges": dict(self.gauges)}


def peak_device_memory() -> int | None:
    """Max ``peak_bytes_in_use`` over devices, or None when the backend
    does not report memory stats (CPU typically does not)."""
    import jax

    peak = None
    for dev in jax.devices():
        try:
            stats = dev.memory_stats()
        except Exception:
            continue
        if not stats:
            continue
        v = stats.get("peak_bytes_in_use", stats.get("bytes_in_use"))
        if v is not None:
            peak = max(peak or 0, int(v))
    return peak
