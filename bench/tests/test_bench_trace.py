"""The trace reduction: busy union, per-scope time, idle gaps by host span."""
import gzip
import json
import os

import pytest

from bench import trace
from bench.tests.conftest import REPO

MS = 1_000_000  # ns


def _events():
    # window 0..100 ms; ops at 10-30 (force), 25-40 (overlapping rebuild),
    # 60-70 (integrate), 70.005-80 (no scope); the host is inside
    # "bench.chunk" from 0 to 100 and "bench.restart" from 40 to 60
    return {
        "devices": {"/device:TPU:0": [
            [10 * MS, 20 * MS, "fusion.1", "force"],
            [25 * MS, 15 * MS, "fusion.2", "rebuild"],
            [60 * MS, 10 * MS, "fusion.3", "integrate"],
            [70 * MS + 5000, 10 * MS - 5000, "copy.4", "other"],
            [150 * MS, 10 * MS, "outside", "force"]]},
        "host": [[0.0, 100 * MS, "bench.window"],
                 [0.0, 100 * MS, "bench.chunk"],
                 [40 * MS, 20 * MS, "bench.restart"]],
    }


def test_summary_of_a_known_timeline():
    s = trace.summarize(_events())
    assert s["window_s"] == pytest.approx(0.1)
    assert s["busy_s"] == pytest.approx(0.030 + 0.010 + 0.009995)
    assert s["scopes"] == pytest.approx({"force": 0.02, "rebuild": 0.015,
                                         "integrate": 0.01,
                                         "other": 0.009995})
    gaps = dict(s["breakdown"]["idle_gaps"])
    assert gaps["bench.restart"] == pytest.approx(0.020)   # 40..60
    assert gaps["bench.chunk"] == pytest.approx(0.010 + 0.020)  # 0..10, 80..100
    assert gaps[trace.SMALL_GAP] == pytest.approx(5e-6)    # 70..70.005
    top = s["breakdown"]["device_ops"][0]
    assert top == ["repro.force:fusion.1", pytest.approx(0.02)]


def test_scope_is_the_innermost_repro_scope():
    assert trace._scope("jit(chunk)/while/body/repro.integrate/repro.force/"
                        "dot") == "force"
    assert trace._scope("jit(f)/mul") == "other"
    assert trace._scope(None) == "other"


def test_nested_ops_count_once():
    ops = [[0.0, 100.0, "while.1", "other"], [10.0, 30.0, "fusion.1", "force"],
           [50.0, 20.0, "conditional.1", "other"],
           [55.0, 10.0, "fusion.2", "rebuild"], [120.0, 5.0, "copy.1", "other"]]
    assert trace._self_time(ops) == [50.0, 30.0, 10.0, 10.0, 5.0]


def test_a_trace_without_device_ops_is_refused():
    with pytest.raises(ValueError):
        trace.summarize({"devices": {}, "host": []})


def test_recorded_chip_trace():
    """Chunks and restarts of the NEP engine at 512 atoms, traced on a TPU
    v5e and reduced to events by :func:`trace.load` (host events of the
    Python function tracer left out)."""
    with gzip.open(os.path.join(REPO, "bench", "testdata",
                                "nep_512_events.json.gz"), "rt") as f:
        events = json.load(f)
    s = trace.summarize(events)
    assert 0 < s["busy_s"] <= s["window_s"]
    assert {"force", "rebuild", "integrate", "observe"} <= set(s["scopes"])
    # own times: a while op does not count the body ops it spans
    assert sum(s["scopes"].values()) == pytest.approx(s["busy_s"], rel=1e-6)
    assert len(s["breakdown"]["device_ops"]) == 10
    names = [n for n, _ in s["breakdown"]["device_ops"]]
    assert any(n.startswith("repro.force:") for n in names)


def test_load_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    trace.start(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = trace.load(str(tmp_path))
    assert any(name == trace.WINDOW for _, _, name in ev["host"])
    s = trace.summarize(ev)
    assert 0 < s["busy_s"] <= s["window_s"]
