"""Telemetry acceptance: run-scoped halo ledger, health monitoring with
abort-and-resume, runlog + report, and the compile watchdog.

The PR-6 acceptance tests:

* the halo exchange ledger is RUN-scoped: two back-to-back runs report
  identical per-run counts/bytes (the process-global ``TRACE`` used to
  accumulate across runs - the latent bug this PR fixes);
* NaN injection mid-run (a schedule that goes non-finite after the first
  chunk) raises a structured :class:`HealthError` naming the last-good
  checkpoint, and restoring that checkpoint resumes a finite trajectory -
  on the flat plan in-process and on the 2-device sharded plan in a
  subprocess;
* a clean run passes energy-drift / spin-norm thresholds and lands its
  health signals in ``EngineTrace.health`` and the runlog;
* migration overflow routes through :class:`HealthError` with per-device
  drop counts and the offending chunk index;
* the compile watchdog observes ZERO recompiles across a schedule-driven
  sharded run (asserted from the runlog's per-chunk compile deltas);
* ``launch/report.py`` renders a runlog without error.
"""
import json
import math
import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hamiltonian import HeisenbergDMIModel
from repro.ensemble import protocol
from repro.md.engine import Engine
from repro.md.integrator import IntegratorConfig
from repro.md.lattice import simple_cubic
from repro.md.state import init_state
from repro.parallel.plan import Sharded
from repro.telemetry import HealthConfig, Telemetry
from repro.telemetry.monitor import HealthError
from repro.telemetry.runlog import read_runlog


def _engine(plan=None, seed=3, temperature=None, field=None,
            cfg=IntegratorConfig(dt=2e-3, spin_alpha=0.05, lattice_gamma=1.0),
            **kw):
    lat = simple_cubic()
    st = init_state(lat, (4, 4, 4), temperature=300.0, spin_init="helix_x",
                    key=jax.random.PRNGKey(seed))
    return Engine(potential=HeisenbergDMIModel(d0=0.008), cfg=cfg,
                  state=st, masses=jnp.asarray(lat.masses),
                  magnetic=jnp.asarray(lat.moments) > 0, cutoff=5.0,
                  capacity=8, skin=0.2, plan=plan, temperature=temperature,
                  field=field, observables=("energy", "magnetization"),
                  **kw)


def _nan_after(t_nan=0.021, hold=(0.0, 0.0, 5.0)):
    """Field schedule that goes NaN strictly after ``t_nan`` [ps]."""
    nan3 = [float("nan")] * 3
    return protocol.piecewise([0.0, t_nan, t_nan, 1.0],
                              [list(hold), list(hold), nan3, nan3])


# ---------------------------------------------------------------------------
# run-scoped halo ledger (the TRACE accumulation bug)
# ---------------------------------------------------------------------------

def test_halo_ledger_is_run_scoped():
    """Two identical back-to-back runs report identical per-run halo
    counts and bytes; the process-global TRACE keeps accumulating (it is
    only a deprecated tee target)."""
    from repro.parallel.halo import TRACE

    snaps = []
    global_before = dict(TRACE.counts)
    for seed in (3, 3):
        eng = _engine(plan=Sharded(), seed=seed)
        eng.run(20, jax.random.PRNGKey(1), chunk=10)
        snaps.append(eng.halo_ledger.snapshot())
    assert snaps[0] == snaps[1], snaps
    assert snaps[0]["counts"], "ledger recorded no exchanges"
    assert snaps[0]["bytes_per_step"] > 0, snaps[0]
    # the global alias still tees (back-compat), hence accumulates
    assert sum(TRACE.counts.values()) >= sum(global_before.values()) + \
        2 * sum(snaps[0]["counts"].values())


# ---------------------------------------------------------------------------
# health monitoring: NaN injection, thresholds, overflow routing
# ---------------------------------------------------------------------------

def test_nan_injection_raises_health_error_with_checkpoint_flat():
    """A schedule that goes NaN mid-run trips the non-finite guard at the
    next chunk boundary; the error names the last-good checkpoint and
    restoring it resumes a finite trajectory."""
    with tempfile.TemporaryDirectory() as d:
        runlog = os.path.join(d, "run.jsonl")
        eng = _engine(field=_nan_after())
        with pytest.raises(HealthError) as ei:
            eng.run(20, jax.random.PRNGKey(1), chunk=10, checkpoint_dir=d,
                    telemetry=Telemetry(runlog=runlog))
        err = ei.value
        assert err.chunk_index == 1, err.chunk_index
        assert err.signals["nonfinite"] > 0, err.signals
        assert err.checkpoint_path is not None
        assert os.path.exists(err.checkpoint_path), err.checkpoint_path
        assert "last-good checkpoint" in str(err)

        # the failed run's runlog records the failure (flight recorder)
        events = read_runlog(runlog)
        assert events[-1]["event"] == "run_end"
        assert events[-1]["status"] == "failed"
        recs = [e for e in events if e["event"] == "chunk"]
        assert recs[-1]["verdict"] == "fail"
        assert "error" in recs[-1]

        # abort-and-resume: a clean engine restores the checkpoint
        clean = _engine(field=jnp.asarray([0.0, 0.0, 5.0]))
        key = clean.restore(d)
        clean.run(10, key, chunk=10)
        assert np.isfinite(np.asarray(clean.state.pos)).all()
        assert np.isfinite(np.asarray(clean.state.spin)).all()
    # the partial trace (chunks up to the abort) kept its health rows
    assert eng.trace.health is not None
    assert eng.trace.health["nonfinite"].shape == (2,)
    assert eng.trace.health["nonfinite"][-1] > 0


def test_clean_run_passes_thresholds():
    """An NVE run passes tight drift/spin-norm thresholds over 2 chunks,
    health signals land in EngineTrace.health, verdicts in the runlog."""
    with tempfile.TemporaryDirectory() as d:
        runlog = os.path.join(d, "run.jsonl")
        eng = _engine()  # temperature=None -> NVE
        eng.run(20, jax.random.PRNGKey(4), chunk=10,
                telemetry=Telemetry(
                    runlog=runlog,
                    health=HealthConfig(max_energy_drift=0.2,
                                        max_spin_dev=1e-3)))
        h = eng.trace.health
        assert set(h) >= {"e_drift", "spin_dev", "nonfinite", "nbr_occ"}
        assert all(v.shape == (2,) for v in h.values())
        assert h["nonfinite"].sum() == 0
        assert np.abs(h["e_drift"]).max() < 0.2
        assert h["spin_dev"].max() < 1e-3
        events = read_runlog(runlog)
        recs = [e for e in events if e["event"] == "chunk"]
        assert [r["verdict"] for r in recs] == ["ok", "ok"]
        assert all("e_drift" in r["health"] for r in recs)
        assert events[-1]["status"] == "ok"
        assert events[-1]["metrics"]["counters"]["steps"] == 20


def test_threshold_violation_is_structured():
    """An absurdly tight drift threshold fails with the offending chunk
    and signal values attached (thermostatted run so drift is nonzero)."""
    eng = _engine(temperature=300.0)
    with pytest.raises(HealthError) as ei:
        eng.run(10, jax.random.PRNGKey(5), chunk=10,
                telemetry=Telemetry(
                    health=HealthConfig(max_energy_drift=1e-12)))
    err = ei.value
    assert err.chunk_index == 0
    assert "energy drift" in str(err)
    assert math.isfinite(err.signals["e_drift"])
    assert err.checkpoint_path is None  # run was not checkpointing


def test_migration_overflow_routes_health_error():
    """The PR-4 overflow raise now reports per-device drop counts, the
    offending chunk, and the last-good checkpoint via HealthError."""
    eng = _engine(plan=Sharded(), seed=5)
    eng.run(10, jax.random.PRNGKey(1), chunk=10)
    eng._carry = eng._carry._replace(
        n_dropped=jnp.asarray([3], jnp.int32))
    eng._last_ckpt = "/tmp/fake-ckpt"
    with pytest.raises(HealthError) as ei:
        eng._check_dropped(chunk_index=4)
    err = ei.value
    assert isinstance(err, RuntimeError)  # pre-telemetry catch keeps working
    assert "overflow" in str(err)
    assert err.chunk_index == 4
    assert err.signals["dropped"] == 3
    assert err.signals["dropped_per_device"] == {0: 3}
    assert err.checkpoint_path == "/tmp/fake-ckpt"


# ---------------------------------------------------------------------------
# runlog + report
# ---------------------------------------------------------------------------

def test_runlog_schema_and_report_renders():
    with tempfile.TemporaryDirectory() as d:
        runlog = os.path.join(d, "run.jsonl")
        eng = _engine()
        eng.run(20, jax.random.PRNGKey(6), chunk=10, telemetry=runlog)
        events = read_runlog(runlog)
        assert [e["event"] for e in events] == \
            ["run_start", "chunk", "chunk", "run_end"]
        start = events[0]
        assert start["schema"] == 1
        assert start["plan"] == "SingleDevice"
        assert start["provenance"]["jax_version"] == jax.__version__
        for rec in events[1:3]:
            assert {"steps", "steps_per_s", "wall_s", "compiles", "halo",
                    "health", "verdict", "chunk_cache"} <= set(rec)
        assert events[1]["compiles"] >= 1      # warmup chunk compiles
        assert events[2]["compiles"] == 0      # steady state does not

        from repro.launch.report import runlog_report
        text = runlog_report(runlog)
        assert "Run report" in text
        assert "steps/s" in text
        assert "health" in text


def test_telemetry_requires_fused_path():
    from repro.md.simulate import Simulation

    lat = simple_cubic()
    st = init_state(lat, (4, 4, 4), temperature=300.0, spin_init="helix_x",
                    key=jax.random.PRNGKey(0))
    sim = Simulation(potential=HeisenbergDMIModel(d0=0.008),
                     cfg=IntegratorConfig(dt=2e-3), state=st,
                     masses=jnp.asarray(lat.masses),
                     magnetic=jnp.asarray(lat.moments) > 0, cutoff=5.0,
                     capacity=8, skin=0.2, fused=False)
    with pytest.raises(ValueError, match="fused"):
        sim.run(10, jax.random.PRNGKey(1), chunk=10, telemetry="x.jsonl")


def test_bad_telemetry_type_rejected():
    eng = _engine()
    with pytest.raises(TypeError, match="telemetry"):
        eng.run(10, jax.random.PRNGKey(1), chunk=10, telemetry=42)


def test_counters_are_the_programs_own():
    """Engine.counters(): chunks and steps run, carries (re)built from the
    state and the table builds they ran, the carry's in-scan builds, and a
    force call per step, per in-scan build and per restart."""
    eng = _engine(temperature=900.0)
    assert eng.counters() == {"chunks": 0, "steps": 0, "restarts": 1,
                              "restart_builds": 1, "force_calls": 1,
                              "rebuilds": 0}
    key = jax.random.PRNGKey(1)
    eng.run(40, key, chunk=20)
    eng.state = eng.state._replace(pos=eng.state.pos + 0.01)  # a restart
    eng.run(20, key, chunk=20)
    c = eng.counters()
    assert (c["chunks"], c["steps"], c["restarts"],
            c["restart_builds"]) == (3, 60, 2, 2)
    assert c["rebuilds"] == eng.n_rebuilds > 0
    assert c["force_calls"] == c["steps"] + c["rebuilds"] + c["restarts"]


def test_counters_count_midpoint_force_calls():
    cfg = IntegratorConfig(dt=2e-3, midpoint=True, midpoint_iters=2)
    eng = _engine(cfg=cfg)
    c0 = eng.counters()
    eng.run(10, jax.random.PRNGKey(1), chunk=5)
    c = {k: v - c0[k] for k, v in eng.counters().items()}
    assert c["steps"] == 10 and c["restarts"] == 0
    assert c["force_calls"] == 10 * (1 + 2 * 2) + c["rebuilds"]


# ---------------------------------------------------------------------------
# 2-device sharded plan: NaN abort-and-resume + compile watchdog
# ---------------------------------------------------------------------------

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json, os.path, tempfile
import jax
import jax.numpy as jnp
import numpy as np
from repro.core.hamiltonian import HeisenbergDMIModel
from repro.ensemble import protocol
from repro.md.engine import Engine
from repro.md.integrator import IntegratorConfig
from repro.md.lattice import simple_cubic
from repro.md.state import init_state
from repro.parallel.plan import Sharded
from repro.telemetry import HealthConfig, Telemetry
from repro.telemetry.monitor import HealthError
from repro.telemetry.runlog import read_runlog

lat = simple_cubic()

def mk(field=None, temp=None):
    st = init_state(lat, (8, 6, 6), temperature=300.0, spin_init="helix_x",
                    key=jax.random.PRNGKey(0))
    return Engine(potential=HeisenbergDMIModel(d0=0.008),
                  cfg=IntegratorConfig(dt=2e-3, spin_alpha=0.05,
                                       lattice_gamma=1.0),
                  state=st, masses=jnp.asarray(lat.masses),
                  magnetic=jnp.asarray(lat.moments) > 0, cutoff=5.0,
                  capacity=16, skin=0.2, plan=Sharded(), temperature=temp,
                  field=field, observables=("energy", "magnetization"))

out = {}

# ---- NaN injection on the sharded plan: abort-and-resume ------------------
nan3 = [float("nan")] * 3
hold = [0.0, 0.0, 5.0]
nanf = protocol.piecewise([0.0, 0.021, 0.021, 1.0],
                          [hold, hold, nan3, nan3])
with tempfile.TemporaryDirectory() as d:
    runlog = os.path.join(d, "run.jsonl")
    eng = mk(field=nanf)
    err = None
    try:
        eng.run(20, jax.random.PRNGKey(1), chunk=10, checkpoint_dir=d,
                telemetry=Telemetry(runlog=runlog))
    except HealthError as e:
        err = e
    events = read_runlog(runlog)
    clean = mk(field=jnp.asarray(hold))
    key = clean.restore(d)
    clean.run(10, key, chunk=10)
    out["nan"] = {
        "raised": err is not None,
        "chunk_index": getattr(err, "chunk_index", None),
        "nonfinite": int(err.signals.get("nonfinite", 0)) if err else 0,
        "ckpt_exists": bool(err is not None and err.checkpoint_path
                            and os.path.exists(err.checkpoint_path)),
        "runlog_status": events[-1].get("status"),
        "resumed_finite": bool(
            np.isfinite(np.asarray(clean.state.pos)).all()
            and np.isfinite(np.asarray(clean.state.spin)).all()),
    }

# ---- compile watchdog: 0 recompiles across a schedule-driven run ----------
temp, field = protocol.field_cooling(300.0, 50.0, 25.0, t_hold=0.004,
                                     t_ramp=0.02)
with tempfile.TemporaryDirectory() as d:
    runlog = os.path.join(d, "run.jsonl")
    eng = mk(field=field, temp=temp)
    eng.run(40, jax.random.PRNGKey(2), chunk=10,
            telemetry=Telemetry(runlog=runlog,
                                health=HealthConfig(max_spin_dev=1e-3)))
    events = read_runlog(runlog)
    recs = [e for e in events if e.get("event") == "chunk"]
    ledger = eng.halo_ledger.snapshot()
    out["watchdog"] = {
        "n_chunks": len(recs),
        "warmup_compiles": recs[0]["compiles"],
        "steady_compiles": sum(r["compiles"] for r in recs[1:]),
        "verdicts": sorted({r["verdict"] for r in recs}),
        "halo_matches_ledger": all(r["halo"] == ledger for r in recs),
        "bytes_per_step": ledger["bytes_per_step"],
        "status": events[-1]["status"],
    }
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def sharded_result():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=1800,
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-3000:]
    line = [ln for ln in r.stdout.splitlines()
            if ln.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


def test_nan_injection_sharded_abort_and_resume(sharded_result):
    res = sharded_result["nan"]
    assert res["raised"], res
    assert res["chunk_index"] == 1, res
    assert res["nonfinite"] > 0, res
    assert res["ckpt_exists"], res
    assert res["runlog_status"] == "failed", res
    assert res["resumed_finite"], res


def test_zero_recompiles_schedule_driven_sharded(sharded_result):
    """The compile watchdog across 4 schedule-driven sharded chunks: the
    warmup chunk compiles, every later chunk compiles NOTHING (knot values
    are runtime data), and every chunk record's halo field equals the
    run-scoped ledger snapshot."""
    res = sharded_result["watchdog"]
    assert res["n_chunks"] == 4, res
    assert res["warmup_compiles"] >= 1, res
    assert res["steady_compiles"] == 0, res
    assert res["verdicts"] == ["ok"], res
    assert res["halo_matches_ledger"], res
    assert res["bytes_per_step"] > 0, res
    assert res["status"] == "ok", res
