"""``correct`` comes out false for the control and for a broken timed path.

The control is the plain reference in bfloat16 put in the program's place;
the faults are planted in the program underneath a whole run at a small
size on the CPU: a step that returns its state unchanged, a force altered
where it is produced, a neighbour table that drops a pair, an in-scan
rebuild that never fires, and rows laid out apart from the cell sort; and
on a 2x2 ``Sharded`` plan (four CPU devices, a process of its own): the
halo's contributions zeroed in the adjoint fold, the forward halo exchange
taken from each device's own block, every device's random numbers drawn
from device 0's key, and the in-scan migration skipped.
"""
import json
import os

import pytest

from bench import check, control, harness
from bench.tests.conftest import sharded_root
from bench.tests.sharded_run import run

SEED = 11


def test_control_fails_and_program_passes(small_root):
    lim = check.limits(small_root, "fege-heisenberg-dmi")
    (seed, prog, ctrl), = control.readings(
        small_root, "heis-fc-64k", [SEED], require_accelerator=False)
    assert check.verdict(prog, lim), prog
    assert not check.verdict(ctrl, lim), ctrl


def _run(root):
    rc, res = harness.run_cell(root, "heis-fc-64k", SEED, 0.5, False,
                               require_accelerator=False)
    assert rc == 0
    return res


def _long_episodes(root):
    """Episodes long enough that the small system rebuilds in the scan."""
    path = os.path.join(root, "bench", "traffic", "fc-hold-ep100.json")
    with open(path) as f:
        traffic = json.load(f)
    traffic.update(episode_steps=60, chunk_steps=10)
    with open(path, "w") as f:
        json.dump(traffic, f)


def test_step_that_leaves_the_state_unchanged(small_root, monkeypatch):
    import repro.md.engine as engine

    real = engine.make_fused_step

    def frozen(*a, **kw):
        step = real(*a, **kw)

        def idle(state, ff, nbh, key, temperature=None, field=None):
            return state._replace(step=state.step + 1), ff, nbh
        del step
        return idle

    monkeypatch.setattr(engine, "make_fused_step", frozen)
    res = _run(small_root)
    assert res["correct"] is False
    assert res["checks"]["chunk_pos"]["value"] > \
        res["checks"]["chunk_pos"]["limit"]


def test_force_altered_where_it_is_produced(small_root, monkeypatch):
    from repro.core.hamiltonian import HeisenbergDMIModel

    real = HeisenbergDMIModel.compute

    def altered(self, nbh, spin, types, field=None):
        e, f, h = real(self, nbh, spin, types, field)
        return e, f.at[0].multiply(-1.0), h

    monkeypatch.setattr(HeisenbergDMIModel, "compute", altered)
    res = _run(small_root)
    assert res["correct"] is False
    assert res["checks"]["restart_F"]["value"] > \
        res["checks"]["restart_F"]["limit"]


def test_table_that_drops_a_pair(small_root, monkeypatch):
    import repro.md.engine as engine

    real = engine.make_table_builder

    def dropping(*a, **kw):
        build, n_cells, use_cell = real(*a, **kw)

        def build_minus_one(pos, box):
            t = build(pos, box)
            return t._replace(mask=t.mask.at[0, 0].set(False))
        return build_minus_one, n_cells, use_cell

    monkeypatch.setattr(engine, "make_table_builder", dropping)
    res = _run(small_root)
    assert res["correct"] is False
    assert res["checks"]["table_pairs"]["value"] >= 1


def test_in_scan_rebuild_that_never_fires(small_root, monkeypatch):
    import jax.numpy as jnp

    import repro.md.engine as engine

    _long_episodes(small_root)
    monkeypatch.setattr(engine, "needs_rebuild",
                        lambda *a, **kw: jnp.asarray(False))
    res = _run(small_root)
    assert res["correct"] is False
    assert res["checks"]["chunk_pos"]["value"] == float("inf")


def test_rows_laid_out_apart_from_the_cell_sort(small_root, monkeypatch):
    import repro.md.engine as engine

    real = engine.cell_order
    monkeypatch.setattr(engine, "cell_order",
                        lambda *a, **kw: real(*a, **kw)[::-1])
    res = _run(small_root)
    assert res["correct"] is False
    assert res["checks"]["layout_rows"]["value"] >= 1


@pytest.fixture(scope="module")
def sharded_cache(tmp_path_factory):
    """One compile cache for the sharded faults' runs, which share every
    program that their fault leaves unchanged."""
    return str(tmp_path_factory.mktemp("compile_cache"))


@pytest.mark.parametrize("fault, episodes, number", [
    ("halo_zeroed", None, "restart_F"),
    ("halo_local", None, "table_pairs"),
    ("device0_key", None, "chunk_vel"),
    # the migration only runs in a rebuild, which needs longer episodes
    ("no_migration", {"episode_steps": 60, "chunk_steps": 10},
     "layout_rows"),
])
def test_sharded_path_broken(tmp_path, sharded_cache, fault, episodes,
                             number):
    root = sharded_root(tmp_path, "fege-heisenberg-dmi", episodes)
    res = run(root, "fege-heisenberg-dmi-2x2", fault, sharded_cache)
    assert res["correct"] is False
    assert res["checks"][number]["value"] > res["checks"][number]["limit"]


@pytest.mark.parametrize("name", check.NAMES)
def test_every_number_has_a_limit(name, small_root):
    for cfg in ("fege-nep-spin", "fege-heisenberg-dmi"):
        assert name in check.limits(small_root, cfg)
