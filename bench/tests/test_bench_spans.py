"""Sub-phase scopes, host spans and program counters: the program names
them, :mod:`bench.spans` reduces them, and the reduction of
:mod:`bench.trace` is unchanged by them."""
import gzip
import json
import os
import re
import tempfile

import pytest

from bench import harness, spans, trace
from bench.tests.conftest import REPO, SHORT, SMALL

MS = 1_000_000  # ns
PATH = "jit(chunk)/repro.loop/while/body"
REB = PATH + "/cond/branch_1_fun/repro.rebuild"
STEP = PATH + "/repro.integrate"


@pytest.mark.parametrize("path, phase, parts", [
    (REB + "/repro.rebuild.order/gather", "rebuild", ["rebuild.order"]),
    (REB + "/repro.rebuild.bin/sort", "rebuild", ["rebuild.bin"]),
    (REB + "/repro.rebuild.search/top_k", "rebuild", ["rebuild.search"]),
    (REB + "/repro.rebuild.gather/sub", "rebuild", ["rebuild.gather"]),
    (REB + "/repro.force.after_build/repro.force/jit(nep_compute)/"
     "repro.force.adjoint/take", "force",
     ["force.after_build", "force.adjoint"]),
    (STEP + "/repro.force/jit(nep_compute)/repro.force.spins/gather",
     "force", ["force.spins"]),
    (STEP + "/repro.force/jit(nep_compute)/repro.force.atom_pass/"
     "nep_atom_pass", "force", ["force.atom_pass"]),
    (STEP + "/repro.force/jit(nep_compute)/repro.force.force_pass/"
     "nep_force_pass", "force", ["force.force_pass"]),
    (STEP + "/repro.force/repro.force.energy_grad/transpose(jvp(etot))/mul",
     "force", ["force.energy_grad"]),
    (STEP + "/repro.force/repro.force.assemble/scatter-add", "force",
     ["force.assemble"]),
    (STEP + "/repro.integrate.refresh/sub", "integrate",
     ["integrate.refresh"]),
    (PATH + "/gt", "loop", []),
    ("jit(_flat_observation)/repro.sync/sort", "sync", []),
])
def test_sub_scopes_keep_the_phase(path, phase, parts):
    assert trace._scope(path) == phase
    assert spans.parts_of(path) == parts


HOST = {"repro.run", "repro.restart", "repro.sync", "repro.chunk",
        "repro.chunk.lower", "repro.chunk.enqueue", "repro.chunk.wait",
        "repro.chunk.gate"}
PARTS = {"rebuild.order", "rebuild.bin", "rebuild.search", "rebuild.gather",
         "force.after_build", "integrate.refresh"}
FORCE = {"nep-fc-64k": {"force.spins", "force.atom_pass", "force.adjoint",
                        "force.force_pass"},
         "heis-fc-64k": {"force.energy_grad", "force.assemble"}}


def _op_names(lowered) -> list:
    """Scope paths of a compiled program's ops, as a device trace gives
    them (``tf_op``); the instructions of reducers and comparators and the
    parameters carry short names of their own and are left out."""
    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    return [n for n in names if n.startswith("jit(")]


def _small_cell(workload):
    cell = harness.load_cell(REPO, workload)
    cell.config.update(SMALL)
    cell.traffic.update(SHORT)
    return cell


def test_engine_programs_and_run_name_every_sub_phase_and_span():
    """At 512 atoms on the CPU: the chunk, restart and sync programs name
    every sub-phase of the engine and the autodiff force, and every op of
    theirs carries a ``repro.*`` scope (an XLA:CPU trace keeps no scope
    paths, so the compiled programs are read); a traced ``Engine.run``
    holds every host span."""
    import jax
    import jax.numpy as jnp

    from bench.builders import system
    from repro.md import engine

    cell = _small_cell("heis-fc-64k")
    states, keys = harness.make_pool(cell.config, cell.traffic, [0])
    eng = system.make_engine(cell.config, cell.traffic,
                             harness.builder(cell).make_potential(
                                 cell.config), states[0],
                             system.make_plan(cell.config, None, states))
    eng.run(10, keys[0][0], chunk=10)
    with tempfile.TemporaryDirectory() as d:
        trace.start(d)
        eng.state = states[0]
        eng.run(10, keys[0][1], chunk=10)
        jax.profiler.stop_trace()
        events = spans.load(d)
    assert HOST <= {name for _, _, name in events["host"]}

    farg = eng._norm_arg(eng.field, vec=True)
    targ = eng._norm_arg(eng.temperature, vec=False)
    n = eng.state.pos.shape[0]
    chunk = _op_names(eng._chunk_fn.lower(
        eng._carry, keys[0][0], eng._chunk_arg(targ, eng._carry, 10),
        eng._chunk_arg(farg, eng._carry, 10), 10, None))
    restart = _op_names(eng._rebuild.lower(
        eng.state, jnp.arange(n, dtype=jnp.int32),
        eng._value_now(farg, vec=True)))
    sync = _op_names(engine._flat_observation.lower(eng._carry))
    found = {p for name in chunk + restart for p in spans.parts_of(name)}
    assert PARTS | FORCE["heis-fc-64k"] <= found
    for names in (chunk, restart):
        assert names and "other" not in {trace._scope(n) for n in names}
    assert {trace._scope(n) for n in sync} == {"sync"}


def test_nep_force_call_names_its_sub_phases():
    """The NEP kernels' force call (its executor on the CPU) names its four
    sub-phases: the neighbour spins, K1, the adjoint gather, K2."""
    import jax
    import jax.numpy as jnp

    from repro.md.neighbor import Neighborhood

    cell = _small_cell("nep-fc-64k")
    pot = harness.builder(cell).make_potential(cell.config)
    n, m = 128, cell.config["neighbor"]["capacity"]

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype)

    nbh = Neighborhood(idx=sds((n, m), jnp.int32),
                       mask=sds((n, m), jnp.bool_),
                       tj=sds((n, m), jnp.int32), dr=sds((n, m, 3)))
    names = _op_names(jax.jit(pot.compute).lower(
        nbh, sds((n, 3)), sds((n,), jnp.int32), sds((3,))))
    assert FORCE["nep-fc-64k"] <= {p for name in names
                                   for p in spans.parts_of(name)}


def _recorded(name):
    with gzip.open(os.path.join(REPO, "bench", "testdata", name), "rt") as f:
        return json.load(f)


def test_the_old_recorded_trace_reduces_as_before():
    """Four-field events: :func:`trace.summarize` reads what it read when
    the trace was recorded, and the sub-scope reduction finds no parts."""
    events = _recorded("nep_512_events.json.gz")
    s = trace.summarize(events)
    assert s["busy_s"] == pytest.approx(0.02900386899199939, rel=1e-12)
    assert s["window_s"] == pytest.approx(0.26599132492200006, rel=1e-12)
    assert s["scopes"] == pytest.approx({
        "other": 0.002768981799998698, "observe": 4.728492199999096e-05,
        "integrate": 0.002030404773999394, "force": 0.005952409916000428,
        "rebuild": 0.018204787579999878}, rel=1e-12)
    assert s["breakdown"]["device_ops"][0] == [
        "repro.rebuild:fusion.9", pytest.approx(0.006453257578000009)]
    assert s["breakdown"]["idle_gaps"][0] == [
        "DeferredTpuAllocator::Allocate", pytest.approx(0.04953458827800077)]
    sp = spans.summarize(events)
    assert sp["parts"] == {} and sp["host_spans"] == {}
    assert sp["unscoped_s"] == pytest.approx(s["scopes"]["other"])


@pytest.mark.parametrize("name, workload", [
    ("nep_512_spans.json.gz", "nep-fc-64k"),
    ("heis_512_spans.json.gz", "heis-fc-64k")])
def test_recorded_chip_traces_with_sub_scopes(name, workload):
    """Chunks and restarts at 512 atoms, traced on a TPU v5e with the
    program's sub-phase scopes and host spans (``bench/spans.py --small
    --events``): every sub-phase and span is there, the phases keep their
    totals, and the five metrics read what the cell's metrics should."""
    events = _recorded(name)
    s = trace.summarize(spans.strip(events))
    sp = spans.summarize(events)
    assert sum(s["scopes"].values()) == pytest.approx(s["busy_s"], rel=1e-6)
    assert sp["unscoped_s"] == pytest.approx(s["scopes"].get("other", 0.0))
    assert PARTS | FORCE[workload] <= set(sp["parts"])
    assert HOST <= set(sp["host_spans"])
    for phase, part in (("rebuild", "rebuild.search"),
                        ("force", sorted(FORCE[workload])[0])):
        assert 0 < sp["parts"][part] <= sp["covered"][phase]
        assert sp["covered"][phase] <= s["scopes"][phase] * (1 + 1e-9)
    ctx = {"run": {"counters": events["counters"]}, "trace": s, "spans": sp}
    got = spans.read_metrics(ctx)
    single = {"nep-fc-64k": "nep_adjoint_ms_per_call",
              "heis-fc-64k": "pair_scatter_ms_per_call"}
    assert set(got) == {"rebuild_ms_per_build", "cell_search_ms_per_build",
                        "host_ms_per_chunk", single[workload]}
    assert all(v > 0 for v in got.values())
    assert got["cell_search_ms_per_build"] < got["rebuild_ms_per_build"]


def _timeline():
    # window 0..100 ms; repro.run 0..100 holds chunk 0..80, its wait
    # 20..60; device ops: a rebuild search 10-30, a rebuild gather 30-35,
    # an after-build adjoint 40-45, a step's adjoint 45-50 and assemble
    # 50-55, an unscoped copy 60-62
    after = "repro.rebuild/repro.force.after_build/repro.force/"
    ops = [[10, 20, "fusion.1", "rebuild", ["rebuild.search"]],
           [30, 5, "fusion.2", "rebuild", ["rebuild.gather"]],
           [40, 5, "fusion.3", "force", spans.parts_of(
               after + "repro.force.adjoint")],
           [45, 5, "fusion.4", "force", ["force.adjoint"]],
           [50, 5, "fusion.5", "force", ["force.assemble"]],
           [60, 2, "copy.6", "other", []]]
    return {"devices": {"/device:TPU:0": [[s * MS, d * MS, *rest]
                                          for s, d, *rest in ops]},
            "host": [[0.0, 100 * MS, "bench.window"],
                     [0.0, 100 * MS, "repro.run"],
                     [0.0, 80 * MS, "repro.chunk"],
                     [20 * MS, 40 * MS, "repro.chunk.wait"]]}


def test_summary_of_a_known_timeline():
    sp = spans.summarize(_timeline())
    assert sp["parts"] == pytest.approx({
        "rebuild.search": 0.020, "rebuild.gather": 0.005,
        "force.after_build": 0.005, "force.adjoint": 0.010,
        "force.assemble": 0.005})
    # the after-build op counts for force only through its adjoint part
    assert sp["covered"] == pytest.approx({"rebuild": 0.025, "force": 0.015})
    assert sp["unscoped_s"] == pytest.approx(0.002)
    run = sp["host_spans"]["repro.run"]
    assert run["count"] == 1 and run["seconds"] == pytest.approx(0.1)
    assert run["unwaited_s"] == pytest.approx(0.060)
    # idle 0-10 under the chunk, 35-40 and 55-60 under its wait, 62-100
    # (its middle past the chunk's end) under the run
    assert sp["idle"] == pytest.approx({"repro.chunk": 0.010,
                                        "repro.chunk.wait": 0.010,
                                        "repro.run": 0.038})
    assert sp["idle_in_repro"] == pytest.approx(1.0)


EXPECTED = {"rebuild_ms_per_build": 25.0 / 5, "cell_search_ms_per_build":
            20.0 / 5, "nep_adjoint_ms_per_call": 10.0 / 4,
            "pair_scatter_ms_per_call": 5.0 / 4,
            "host_ms_per_chunk": 60.0 / 2}


@pytest.mark.parametrize("metric", sorted(spans.METRICS))
def test_metric_readers(metric):
    read = spans.METRICS[metric]
    events = _timeline()
    t = trace.summarize(spans.strip(events))
    counters = {"chunks": 2, "steps": 40, "restarts": 1, "restart_builds": 1,
                "rebuilds": 4, "force_calls": 4}
    ctx = {"run": {"counters": counters}, "trace": t,
           "spans": spans.summarize(events)}
    assert read(ctx) == pytest.approx(EXPECTED[metric])
    assert read({**ctx, "trace": None, "spans": None}) is None
    assert read({**ctx, "run": {}}) is None
    # a program without sub-scopes gives no parts to read
    no_parts = read({**ctx, "spans": spans.summarize(spans.strip(events))})
    if "per_call" in metric or "search" in metric:
        assert no_parts is None
    else:
        assert no_parts == pytest.approx(EXPECTED[metric])


def test_a_cpu_run_reads_counters_and_host_spans(small_root):
    """:func:`spans.run` through the harness at 512 atoms on the CPU: the
    window's counters are the program's own, and the harness's module
    globals are back in place afterwards."""
    saved = trace.load, harness.Episodes, harness.load_cell
    rc, res, out, events = spans.run(small_root, "heis-fc-64k", 5, 1.0,
                                     require_accelerator=False)
    assert (trace.load, harness.Episodes, harness.load_cell) == saved
    assert rc == 0 and res["correct"] is True
    c = out["counters"]
    assert c["steps"] == c["chunks"] * SHORT["chunk_steps"] > 0
    assert c["restarts"] == c["restart_builds"] == res["attempted"] // 2
    assert c["force_calls"] == c["steps"] + c["rebuilds"] + c["restarts"]
    assert out["metrics"]["host_ms_per_chunk"] > 0
    assert out["host_spans"]["repro.chunk"]["count"] == c["chunks"]
    assert events["counters"] == c
