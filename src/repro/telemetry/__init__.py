"""Run-scoped observability for the unified MD engine.

Four pieces, threaded through ``Engine.run(telemetry=...)`` on all plans:

* :mod:`repro.telemetry.metrics` - :class:`RunMetrics` counters/gauges and
  the :class:`CompileWatchdog` (XLA compile events via ``jax.monitoring``).
* :mod:`repro.telemetry.monitor` - in-scan health signals (energy drift,
  spin-norm deviation, NaN/Inf guard, occupancy headroom), chunk-boundary
  threshold checks, and the structured :class:`HealthError` that carries
  the last-good checkpoint path.
* :mod:`repro.telemetry.profiling` - ``named_scope`` phase markers inside
  the compiled step and host ``TraceAnnotation`` spans around the run
  loop; they land in whatever ``jax.profiler`` trace the caller opens.
* :mod:`repro.telemetry.runlog` - the per-chunk JSONL event stream that
  ``launch/report.py`` renders and the planner/serving layers consume.

Entry point::

    tel = Telemetry(runlog="runs/anneal.jsonl",
                    health=HealthConfig(max_spin_dev=1e-3))
    engine.run(n_steps, key, chunk=100, telemetry=tel)

or simply ``engine.run(..., telemetry="runs/anneal.jsonl")``.
"""
from __future__ import annotations

import dataclasses
import os
import time

from repro.telemetry.metrics import (CompileWatchdog, RunMetrics,
                                     peak_device_memory)
from repro.telemetry.monitor import (HealthConfig, HealthError, check_chunk,
                                     nonfinite_count, occupancy_fraction,
                                     spin_norm_dev)
from repro.telemetry.profiling import annotate, phase
from repro.telemetry.runlog import RunLog, append_event, read_runlog

__all__ = [
    "Telemetry", "TelemetrySession", "RunMetrics", "CompileWatchdog",
    "HealthConfig", "HealthError", "RunLog", "read_runlog", "append_event",
    "check_chunk", "nonfinite_count", "occupancy_fraction", "spin_norm_dev",
    "phase", "annotate", "peak_device_memory", "as_telemetry",
]


@dataclasses.dataclass
class Telemetry:
    """Run observability config handed to ``Engine.run(telemetry=...)``.

    One object bundles the two opt-in surfaces of a monitored run:
    the JSONL ``runlog`` (per-chunk throughput, compile-watchdog deltas,
    halo-ledger bytes, drift, health verdict - the machine-readable
    record ``repro.launch.report`` renders and the serving accounting
    replays) and the ``health`` thresholds checked at every chunk
    boundary (raising :class:`HealthError`; ``None`` disables checking,
    signals are still computed into ``engine.trace.health``).
    ``append=True`` continues an existing runlog instead of truncating
    it - retry segments and packed serving segments share one file that
    way.  A bare path passed to
    ``Engine.run`` is shorthand for ``Telemetry(runlog=path)``
    (:func:`as_telemetry`)."""

    runlog: str | os.PathLike | None = None    # JSONL event stream path
    health: HealthConfig | None = dataclasses.field(
        default_factory=HealthConfig)          # None disables checking
    metrics: RunMetrics = dataclasses.field(default_factory=RunMetrics)
    append: bool = False     # append to an existing runlog (retry segments)


def as_telemetry(telemetry) -> "Telemetry | None":
    """Normalize ``None | str path | Telemetry`` to a Telemetry object."""
    if telemetry is None or isinstance(telemetry, Telemetry):
        return telemetry
    if isinstance(telemetry, (str, os.PathLike)):
        return Telemetry(runlog=telemetry)
    raise TypeError(f"telemetry must be a path or Telemetry, got "
                    f"{type(telemetry).__name__}")


class TelemetrySession:
    """Drives one run's telemetry: wall clocks, compile deltas, halo
    accounting, runlog records.  Created by ``Engine.run`` when a
    :class:`Telemetry` config is passed; the engine feeds it one
    :meth:`chunk` call per chunk boundary and one :meth:`finish`."""

    def __init__(self, tel: Telemetry, *, ledger, run_info: dict):
        self.tel = tel
        self.metrics = tel.metrics
        self.ledger = ledger
        self.watchdog = CompileWatchdog()
        self._compile_mark = self.watchdog.mark()
        self._t0 = time.perf_counter()
        self._steps = 0
        self._chunks = 0
        self.runlog = (RunLog(tel.runlog, mode="a" if tel.append else "w")
                       if tel.runlog else None)
        if self.runlog is not None:
            self.runlog.run_start(**run_info)

    # ------------------------------------------------------------------
    def chunk(self, *, steps: int, step: int, time_ps: float, wall_s: float,
              health: dict, verdict: str, chunk_cache: int,
              counters: dict | None = None, error: str | None = None) -> dict:
        """Record one chunk boundary; returns the runlog record."""
        compiles = self.watchdog.since(self._compile_mark)
        self._compile_mark = self.watchdog.mark()
        self._steps += steps
        self._chunks += 1
        steps_per_s = steps / wall_s if wall_s > 0 else float("inf")
        halo = self.ledger.snapshot() if self.ledger is not None else None

        self.metrics.inc("steps", steps)
        self.metrics.inc("chunks")
        self.metrics.inc("compiles", compiles)
        self.metrics.inc("wall_s", wall_s)
        for name, value in (counters or {}).items():
            self.metrics.inc(name, value)
        self.metrics.set("steps_per_s", steps_per_s)
        self.metrics.set("chunk_cache", chunk_cache)
        if halo is not None:
            self.metrics.set("halo_bytes_per_step", halo["bytes_per_step"])

        record = {
            "chunk": self._chunks - 1, "steps": steps, "step": step,
            "time_ps": time_ps, "wall_s": wall_s, "steps_per_s": steps_per_s,
            "compiles": compiles, "chunk_cache": chunk_cache,
            "halo": halo, "health": health, "verdict": verdict,
        }
        if counters:
            record.update(counters)
        if error is not None:
            record["error"] = error
        if self.runlog is not None:
            self.runlog.write("chunk", **record)
        return record

    # ------------------------------------------------------------------
    def finish(self, status: str = "ok", **extra) -> dict | None:
        wall = time.perf_counter() - self._t0
        self.metrics.set("total_wall_s", wall)
        peak = peak_device_memory()
        if peak is not None:
            self.metrics.set("peak_memory_bytes", peak)
        record = None
        if self.runlog is not None:
            record = self.runlog.write(
                "run_end", status=status, total_steps=self._steps,
                total_chunks=self._chunks, total_wall_s=wall,
                steps_per_s=(self._steps / wall if wall > 0 else None),
                peak_memory_bytes=peak, metrics=self.metrics.snapshot(),
                **extra)
            self.runlog.close()
        return record
