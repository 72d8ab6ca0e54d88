"""Bring-up smoke of the coupled spin-lattice engine on a TPU.

Drives the main path once through the normal entry points: the
:class:`repro.md.engine.Engine` running coupled spin-lattice MD of B20 FeGe
under :class:`repro.core.potential.NEPSpinPotential` at the production
``config()`` widths (l_max 4, n_rad 6, n_ang 4, n_spin 4, hidden 32, basis
8, 2 types), with seeded random weights (no trained potential ships with
the repository), a field-cooling protocol and the ``energy`` /
``magnetization`` / ``charge`` observables.

    python chip_smoke.py              # one chip: phases A and B
    python chip_smoke.py --chips 4    # four chips: Sharded vs SingleDevice

One chip runs two phases on the same initial state:

  A. the autodiff evaluator (``use_kernel=False``), under
     ``jax.default_matmul_precision("highest")`` (the plain reference);
  B. the fused NEP kernels (``use_kernel=True``; the platform resolves the
     executor to the Mosaic ``pallas`` kernels, and the compiled
     evaluation must contain ``tpu_custom_call``).

Each phase checks that the first evaluation's energy, forces and effective
fields match the same call on the host CPU backend (f32, highest matmul
precision; phase B's CPU call runs the same kernel bodies through the
``xla_tiled`` executor), that phase B matches phase A on the chip, that
every observable is finite, and that the chunks after the warmup chunk
trigger no backend compile.

``--chips 4`` runs only the ``Sharded`` plan (1-D ``sx`` mesh over the four
chips) on the same state against the one-chip ``SingleDevice`` plan: the
first evaluation, the observables after one chunk at T = 0, and that the
four shards sit on four distinct devices.

Any failed check names itself on stderr and exits 1; so does a run that
finds no TPU.  The last line of standard output, on success only, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# B20 unit cells per box edge: 8 * 20^3 = 64,000 atoms, config()'s
# per-device atom count (65,536) rounded to a whole B20 supercell
CELLS = 20
CELLS_WHY = ("compiled for a described v5e, the phase A chunk needs 6.3 GB "
             "and the phase B chunk 8.7 GB of the chip's 16 GB")
CAPACITY = 72        # neighbor slots: 5 A cutoff + 0.5 A skin holds <= 61
SKIN = 0.5           # A
CELL_CAPACITY = 24   # linked-cell slots (17^3 grid of 5.53 A cells)
DISPLACE = 0.05      # A, seeded random displacement off the perfect lattice
CHUNK = 5            # steps per compiled chunk
N_CHUNKS = 3         # one warmup chunk + two that must not compile
T_HOT, T_COLD, B_FIELD = 300.0, 100.0, 0.2   # K, K, Tesla along z

# relative error bounds: |dE| / max(|E|, 1 eV); max|dF| / max|F|;
# max|dH| / max|H| (f32 on both sides, different transcendental units and
# summation orders)
TOL = {"E": 1e-4, "F": 1e-3, "H": 1e-3}
# four-chip observables after one T = 0 chunk: relative, and absolute for
# the topological charge
TOL_OBS = {"energy": 1e-4, "magnetization": 1e-3, "charge": 1e-2}


class CheckFailed(Exception):
    pass


def check(name: str, ok: bool, detail: str = "") -> None:
    if not ok:
        raise CheckFailed(f"{name}: {detail}")
    print(f"  check {name}: ok {detail}", flush=True)


def rel_errors(got, ref) -> dict:
    import numpy as np
    e, f, h = (np.asarray(x, np.float64) for x in got)
    e0, f0, h0 = (np.asarray(x, np.float64) for x in ref)
    return {"E": float(abs(e - e0) / max(abs(e0), 1.0)),
            "F": float(np.abs(f - f0).max() / max(np.abs(f0).max(), 1e-30)),
            "H": float(np.abs(h - h0).max() / max(np.abs(h0).max(), 1e-30))}


def check_parity(name: str, got, ref) -> dict:
    err = rel_errors(got, ref)
    bad = {k: v for k, v in err.items() if not v <= TOL[k]}
    check(name, not bad, f"errors {err} bounds {TOL}")
    return err


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------

def make_system(seed: int, cells: int):
    """Seeded B20 FeGe state, random-weight NEP-SPIN at config() widths,
    and the field-cooling schedules."""
    import jax
    import jax.numpy as jnp

    from repro.configs.fege_spinlattice import config
    from repro.core.potential import NEPSpinPotential, init_params
    from repro.ensemble import protocol
    from repro.md.lattice import b20_fege
    from repro.md.state import init_state

    mdcfg = config()
    spec = mdcfg.spec
    lat = b20_fege()
    st = init_state(lat, (cells,) * 3, temperature=T_HOT,
                    spin_init="random", key=jax.random.PRNGKey(seed),
                    dtype=jnp.float32)
    kick = DISPLACE * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                        st.pos.shape, jnp.float32)
    st = st._replace(pos=(st.pos + kick) % st.box)
    params = init_params(spec, jax.random.PRNGKey(seed + 2), jnp.float32)
    pot = NEPSpinPotential(spec, params,
                           moments=jnp.asarray(lat.moments, jnp.float32))
    total = N_CHUNKS * CHUNK * mdcfg.dt
    temp, field = protocol.field_cooling(
        T_HOT, T_COLD, B_FIELD, t_hold=0.25 * total, t_ramp=0.5 * total)
    return mdcfg, lat, st, pot, temp, field


def make_engine(mdcfg, lat, st, pot, plan, temperature, field):
    import jax.numpy as jnp

    from repro.md.engine import Engine
    from repro.md.integrator import IntegratorConfig

    return Engine(
        potential=pot,
        cfg=IntegratorConfig(dt=mdcfg.dt, moment=1.16, lattice_gamma=1.0,
                             spin_alpha=0.01),
        state=st, masses=jnp.asarray(lat.masses, jnp.float32),
        magnetic=jnp.asarray(lat.moments) > 0, cutoff=pot.spec.cutoff,
        capacity=CAPACITY, skin=SKIN, use_cell_list=True,
        cell_capacity=CELL_CAPACITY, plan=plan, temperature=temperature,
        field=field, observables=("energy", "magnetization", "charge"))


def evaluate_on(device, pot, nbh, spin, types, field):
    """``pot.compute`` as one program on ``device`` at highest matmul
    precision; returns host (E, F, H_eff)."""
    import jax

    args = jax.device_put((pot.params, pot.moments, nbh, spin, types,
                           field), device)

    def fn(p, m, *a):
        return dataclasses.replace(pot, params=p, moments=m).compute(*a)

    with jax.default_matmul_precision("highest"):
        return jax.device_get(jax.jit(fn)(*args))


def first_eval(eng):
    """Host copy of the engine's construction-time (E, F, H_eff), in the
    original atom order."""
    import jax

    return jax.device_get((eng._ff.energy, eng._ff.force, eng._ff.field))


def occupancy(st, eng) -> tuple[int, int]:
    """(max neighbor-table occupancy, max linked-cell occupancy)."""
    import numpy as np

    from repro.md.neighbor import grid_shape

    nbr = int(np.asarray(eng._carry.table.mask).sum(axis=1).max())
    box = np.asarray(st.box, np.float64)
    grid = np.asarray(grid_shape(box, eng.cutoff, SKIN))
    ijk = np.floor(np.asarray(st.pos, np.float64) / box * grid).astype(int)
    ijk %= grid
    flat = (ijk[:, 0] * grid[1] + ijk[:, 1]) * grid[2] + ijk[:, 2]
    return nbr, int(np.bincount(flat).max())


# ---------------------------------------------------------------------------
# one chip: phases A and B
# ---------------------------------------------------------------------------

def run_phase(label, mdcfg, lat, st, pot, temp, field, field0, cpu, wd,
              seed):
    """Build the engine, check the first evaluation against the host CPU,
    run N_CHUNKS chunks and check observables + steady-state compiles.
    Returns (first-eval ForceField in original atom order, engine)."""
    import jax
    import numpy as np

    from repro.parallel.plan import SingleDevice
    from repro.telemetry.metrics import peak_device_memory

    print(f"phase {label}: use_kernel={pot.use_kernel}", flush=True)
    s0, t0 = wd.seconds, time.perf_counter()
    eng = make_engine(mdcfg, lat, st, pot, SingleDevice(), temp, field)
    jax.block_until_ready(eng._carry)
    print(f"  engine built in {time.perf_counter() - t0:.1f} s "
          f"({wd.seconds - s0:.1f} s of backend compile)", flush=True)
    first = first_eval(eng)
    nbr, cell = occupancy(st, eng)
    check(f"{label}.neighbor_capacity", nbr < CAPACITY,
          f"max neighbor occupancy {nbr} of {CAPACITY}")
    check(f"{label}.cell_capacity", cell <= CELL_CAPACITY,
          f"max linked-cell occupancy {cell} of {CELL_CAPACITY}")

    c = eng._carry
    got = jax.device_get((c.ff.energy, c.ff.force, c.ff.field))
    cpu_pot = (dataclasses.replace(pot, mode="xla_tiled") if pot.use_kernel
               else pot)
    t1 = time.perf_counter()
    ref = evaluate_on(cpu, cpu_pot, c.nbh, c.state.spin, c.state.types,
                      field0)
    print(f"  host CPU reference evaluated in "
          f"{time.perf_counter() - t1:.1f} s", flush=True)
    check_parity(f"{label}.first_eval_vs_cpu", got, ref)

    key = jax.random.PRNGKey(seed + 3)
    key, k_warm = jax.random.split(key)
    s0, t0 = wd.seconds, time.perf_counter()
    eng.run(CHUNK, k_warm, chunk=CHUNK)
    jax.block_until_ready(eng._carry)
    print(f"  warmup chunk ({CHUNK} steps) in "
          f"{time.perf_counter() - t0:.1f} s "
          f"({wd.seconds - s0:.1f} s of backend compile)", flush=True)
    warm = eng.trace
    mark, t0 = wd.mark(), time.perf_counter()
    eng.run(CHUNK * (N_CHUNKS - 1), key, chunk=CHUNK)
    jax.block_until_ready(eng._carry)
    steady = wd.since(mark)
    print(f"  {N_CHUNKS - 1} steady chunks in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    check(f"{label}.compiles_after_warmup", steady == 0,
          f"{steady} backend compiles in {N_CHUNKS - 1} chunks after warmup")
    for name in eng.observables:
        vals = np.concatenate([np.ravel(warm.values[name]),
                               np.ravel(eng.trace.values[name])])
        check(f"{label}.finite_{name}", bool(np.isfinite(vals).all()),
              f"{vals.size} values, last {vals[-1]!r}")
    peak = peak_device_memory()
    check(f"{label}.peak_bytes_in_use", peak is not None,
          f"peak_bytes_in_use {peak}")
    return first, eng


def run_one_chip(seed: int) -> None:
    import jax
    import numpy as np

    from repro.telemetry.metrics import CompileWatchdog

    wd = CompileWatchdog()
    cpu = jax.devices("cpu")[0]
    mdcfg, lat, st, pot, temp, field = make_system(seed, CELLS)
    spec = pot.spec
    print(f"system: B20 FeGe {CELLS}^3 cells = {st.pos.shape[0]} atoms "
          f"({CELLS_WHY}); neighbor capacity {CAPACITY}, skin {SKIN} A",
          flush=True)
    print(f"widths: l_max {spec.l_max} n_rad {spec.n_rad} n_ang "
          f"{spec.n_ang} n_spin {spec.n_spin} hidden {spec.hidden} basis "
          f"{spec.basis_size} types {spec.n_types} (n_desc {spec.n_desc})",
          flush=True)
    field0 = np.asarray(field.at(0.0), np.float32)

    with jax.default_matmul_precision("highest"):
        ff_a, eng = run_phase("A", mdcfg, lat, st, pot, temp, field,
                              field0, cpu, wd, seed)
    del eng

    pot_b = dataclasses.replace(pot, use_kernel=True)
    ff_b, eng = run_phase("B", mdcfg, lat, st, pot_b, temp, field, field0,
                          cpu, wd, seed)
    hlo = eng._rebuild.lower(st, np.arange(st.pos.shape[0], dtype=np.int32),
                             field0).compile().as_text()
    n_custom = hlo.count("tpu_custom_call")
    check("B.tpu_custom_call", n_custom >= 2,
          f"{n_custom} tpu_custom_call ops in the compiled evaluation")
    check_parity("B.first_eval_vs_A", ff_b, ff_a)
    print(f"compile total {wd.seconds:.1f} s over {wd.count} backend "
          f"compiles", flush=True)


# ---------------------------------------------------------------------------
# four chips: Sharded plan vs SingleDevice
# ---------------------------------------------------------------------------

def run_four_chips(seed: int) -> None:
    import jax
    import numpy as np

    from repro.parallel.plan import Sharded, SingleDevice

    devs = jax.devices()
    check("four_chips", len(devs) == 4, f"{len(devs)} devices")
    mdcfg, lat, st, pot, _temp, field = make_system(seed, CELLS)
    field0 = np.asarray(field.at(0.0), np.float32)
    print(f"system: B20 FeGe {CELLS}^3 cells = {st.pos.shape[0]} atoms, "
          f"autodiff evaluator, T = 0, B = {field0.tolist()} T", flush=True)
    with jax.default_matmul_precision("highest"):
        one = make_engine(mdcfg, lat, st, pot, SingleDevice(), 0.0, field0)
        four = make_engine(mdcfg, lat, st, pot, Sharded(devices=tuple(devs)),
                           0.0, field0)
        c = four._carry
        shard_devs = {s.device for s in c.state.pos.addressable_shards}
        check("sharded.distinct_devices", len(shard_devs) == 4,
              f"shards on {sorted(d.id for d in shard_devs)}, mesh "
              f"{four._rplan.describe()}")
        check_parity("sharded.first_eval_vs_single", first_eval(four),
                     first_eval(one))
        key = jax.random.PRNGKey(seed + 3)
        one.run(CHUNK, key, chunk=CHUNK)
        four.run(CHUNK, key, chunk=CHUNK)
    for name in one.observables:
        a = np.asarray(four.trace.values[name], np.float64)
        b = np.asarray(one.trace.values[name], np.float64)
        check(f"sharded.finite_{name}", bool(np.isfinite(a).all()), "")
        if name == "charge":
            err = float(np.abs(a - b).max())
        else:
            err = float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))
        check(f"sharded.{name}_after_chunk", err <= TOL_OBS[name],
              f"error {err:.3e} bound {TOL_OBS[name]} "
              f"(sharded {a.ravel().tolist()}, single {b.ravel().tolist()})")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.utils.compile_cache import use_compile_cache
    cache = use_compile_cache()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke FAILED: no TPU found (jax.devices()[0].platform "
              f"is {dev.platform!r})", file=sys.stderr)
        return 1
    print(f"device: {dev.device_kind} x {len(jax.devices())} "
          f"(jax {jax.__version__}); compile cache {cache}", flush=True)
    try:
        if args.chips == 4:
            run_four_chips(args.seed)
        else:
            run_one_chip(args.seed)
    except CheckFailed as exc:
        print(f"chip_smoke FAILED check {exc}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
