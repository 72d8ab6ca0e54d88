"""Distributed spin-lattice MD step for the dry-run and real multi-device
runs: the paper's whole-application benchmark (neighbor stencil + halo
exchange + NEP-SPIN descriptor/inference + coupled Suzuki-Trotter update +
Langevin/sLLG thermostats at T=160 K, the Fig. 9 protocol).

The lowered step contains exactly ONE fused force/field evaluation
(time-to-solution accounting matches the paper's per-step cost).

``python -m repro.launch.md_step`` additionally runs the production-path
smoke: one schedule-driven chunk of the unified engine
(:class:`repro.md.engine.Engine`, ``Sharded`` plan) on the available
devices, reporting steps/s, in-scan rebuilds, and the per-step halo
exchange ledger - the whole-application cell the dryrun's per-step
lowering approximates, executed for real.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.core.potential import init_params
from repro.md.integrator import ForceField, IntegratorConfig, make_step
from repro.md.state import SpinLatticeState
from repro.parallel.domain import DomainSpec, distributed_energy_fn
from repro.utils import units

# per-device cell grids (paper weak-scaling analogue: small & large cases)
MD_SHAPES = {
    "md_small": (8, 8, 8),
    "md_large": (16, 16, 16),
}


def domain_for_mesh(mesh, cells_per_device, cell_size):
    """Map mesh axes onto the 3-D device grid: data->X, model->Y, pod->Z."""
    axis_map = ["data", "model", "pod" if "pod" in mesh.axis_names else None]
    dev_grid = [mesh.shape.get(a, 1) if a else 1 for a in axis_map]
    cells = tuple(c * g for c, g in zip(cells_per_device, dev_grid))
    box = tuple(c * cell_size for c in cells)
    return DomainSpec(cells=cells, capacity=16, cutoff=5.0, box=box,
                      axis_map=tuple(axis_map))


def build_md_dryrun(shape_name: str, mesh, dtype=jnp.float32,
                    temperature: float = 160.0, midpoint: bool = False,
                    impl: str = "stencil", nbr_capacity: int = 64):
    """Returns (lowered, compiled, meta) for the MD cell.

    impl: 'stencil' (27-shift streaming, the baseline) or 'pruned'
    (pre-staged top-M neighbor table - the paper's Phase-A/B pre-staging;
    the table is an input rebuilt on skin violations, like a KV cache)."""
    from repro.parallel.domain import distributed_energy_fn_pruned
    mdcfg = configs.get("fege-spinlattice")
    spec = mdcfg.spec
    dspec = domain_for_mesh(mesh, MD_SHAPES[shape_name], mdcfg.cell_size)
    dspec.check()

    masses = jnp.asarray([units.MASS_FE, units.MASS_GE], dtype)
    magnetic = jnp.asarray([True, False])
    moments = jnp.asarray([1.16, 0.0], dtype)
    field = jnp.asarray([0.0, 0.0, 0.1], dtype)   # Fig. 9 field protocol

    if impl == "pruned":
        _, effn_p = distributed_energy_fn_pruned(
            spec, dspec, mesh, capacity=nbr_capacity, field=field,
            moments=moments)
    else:
        _, effn = distributed_energy_fn(spec, dspec, mesh, field=field,
                                        moments=moments)
    icfg = IntegratorConfig(
        dt=mdcfg.dt, moment=1.16, midpoint=midpoint, midpoint_iters=2,
        temperature=temperature, lattice_gamma=1.0, spin_alpha=0.01,
        spin_longitudinal=0.1)

    def md_step(params, state: SpinLatticeState, mask, ff: ForceField,
                key, tbl_idx=None, tbl_mask=None):
        types_c = jnp.maximum(state.types, 0)

        if impl == "pruned":
            def evaluate(pos, spin):
                return ForceField(*effn_p(params, pos, spin, types_c,
                                          mask, tbl_idx, tbl_mask))
        else:
            def evaluate(pos, spin):
                return ForceField(*effn.raw(params, pos, spin, types_c,
                                            mask))

        step = make_step(evaluate, icfg, masses, magnetic, atom_mask=mask)
        new_state, new_ff = step(state, ff, key)
        return new_state, new_ff

    # --- abstract inputs (ShapeDtypeStruct only; no allocation) ----------
    cx, cy, cz = dspec.cells
    k = dspec.capacity
    cell = lambda tail, dt: jax.ShapeDtypeStruct(
        (cx, cy, cz, k, *tail), dt,
        sharding=NamedSharding(mesh, dspec.pspec(*([None] * (len(tail)
                                                            + 1)))))
    rep = lambda shape, dt: jax.ShapeDtypeStruct(
        shape, dt, sharding=NamedSharding(mesh, P()))

    params_abs = jax.eval_shape(
        lambda: init_params(spec, jax.random.PRNGKey(0), dtype=dtype))
    params_abs = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=NamedSharding(mesh, P())),
        params_abs)
    state_abs = SpinLatticeState(
        pos=cell((3,), dtype), vel=cell((3,), dtype), spin=cell((3,), dtype),
        types=cell((), jnp.int32), box=rep((3,), dtype),
        step=rep((), jnp.int32))
    mask_abs = cell((), jnp.bool_)
    ff_abs = ForceField(energy=rep((), dtype), force=cell((3,), dtype),
                        field=cell((3,), dtype))
    key_abs = rep((2,), jnp.uint32)

    from repro.utils.jaxpr_cost import lowered_cost
    jitted = jax.jit(md_step, donate_argnums=(1, 3))
    with jax.set_mesh(mesh):
        if impl == "pruned":
            tbl_idx_abs = cell((nbr_capacity,), jnp.int32)
            tbl_mask_abs = cell((nbr_capacity,), jnp.bool_)
            traced = jitted.trace(params_abs, state_abs, mask_abs, ff_abs,
                                  key_abs, tbl_idx_abs, tbl_mask_abs)
        else:
            traced = jitted.trace(params_abs, state_abs, mask_abs, ff_abs,
                                  key_abs)
        lowered = traced.lower()
        compiled = lowered.compile()

    n_atoms = int(np.prod(dspec.cells)) * 13  # ~12.8 B20 atoms per 5.5A cell
    meta = {"kind": "md", "tokens": n_atoms, "atoms": n_atoms,
            "atoms_per_device": n_atoms // mesh.size,
            "cells": dspec.cells, "capacity": k,
            "jaxpr_cost": lowered_cost(traced.jaxpr)}
    return lowered, compiled, meta


# ---------------------------------------------------------------------------
# whole-chunk engine smoke (the production path the dryrun approximates)
# ---------------------------------------------------------------------------

_COMPILES = {"n": 0, "registered": False}


def _compile_counter() -> dict:
    """Process-wide XLA backend-compile counter (listener installed once -
    jax.monitoring listeners cannot be unregistered, so per-call
    registration would double-count on repeated calls)."""
    if not _COMPILES["registered"]:
        def on_event(name, _dur, **kw):
            if name == "/jax/core/compile/backend_compile_duration":
                _COMPILES["n"] += 1
        jax.monitoring.register_event_duration_secs_listener(on_event)
        _COMPILES["registered"] = True
    return _COMPILES


def run_engine_chunk(cells=(8, 6, 6), steps: int = 40, chunk: int = 20,
                     temperature: float = 160.0, kernel: bool = False,
                     seed: int = 0, devices=None) -> dict:
    """Drive one field-cooled chunk of the unified engine on ``devices``
    (default: all of them) and return {steps_per_s, rebuilds, halo ledger,
    ...}.

    ``kernel=True`` routes the fused NEP kernel evaluator through the
    sharded plan instead of the Heisenberg-DMI reference (mode "auto":
    compiled Pallas on TPU, compiled lax.map tiling on CPU).
    """
    import time as _time

    from repro.ensemble import protocol
    from repro.md.engine import Engine
    from repro.md.lattice import simple_cubic
    from repro.md.state import init_state
    from repro.parallel.plan import Sharded

    compiles = _compile_counter()

    mdcfg = configs.get("fege-spinlattice")
    lat = simple_cubic()
    st = init_state(lat, cells, temperature=temperature,
                    spin_init="helix_x", key=jax.random.PRNGKey(seed),
                    dtype=jnp.float32)
    if kernel:
        from repro.core.potential import NEPSpinPotential
        # smoke-sized spec keeps the sharded-orchestration timing cheap
        from repro.configs.fege_spinlattice import smoke_config
        spec = smoke_config().spec
        potential = NEPSpinPotential(
            spec, init_params(spec, jax.random.PRNGKey(0),
                              dtype=jnp.float32),
            use_kernel=True)
    else:
        from repro.core.hamiltonian import HeisenbergDMIModel
        potential = HeisenbergDMIModel(d0=0.01)
    t_end = steps * mdcfg.dt
    temp, field = protocol.field_cooling(
        temperature, temperature / 4, 0.1,
        t_hold=0.2 * t_end, t_ramp=0.6 * t_end)
    icfg = IntegratorConfig(dt=mdcfg.dt, moment=1.16, lattice_gamma=1.0,
                            spin_alpha=0.01)
    eng = Engine(
        potential=potential, cfg=icfg, state=st,
        masses=jnp.asarray(lat.masses, jnp.float32),
        magnetic=jnp.asarray(lat.moments) > 0, cutoff=5.0,
        capacity=16, skin=0.3,
        plan=Sharded(devices=None if devices is None else tuple(devices)),
        temperature=temp, field=field,
        observables=("energy", "magnetization", "charge"))
    eng.run(chunk, jax.random.PRNGKey(1), chunk=chunk)   # compile + warm
    jax.block_until_ready(eng.state.pos)
    c0 = compiles["n"]
    t0 = _time.perf_counter()
    eng.run(steps, jax.random.PRNGKey(2), chunk=chunk)
    jax.block_until_ready(eng.state.pos)
    wall = _time.perf_counter() - t0
    return {
        "devices": eng._rplan.mesh.size,
        "atoms": st.n_atoms,
        "cells": tuple(eng._rplan.dspec.cells),
        "steps_per_s": steps / wall,
        "rebuilds": eng.n_rebuilds,
        "migrated": eng.n_migrated,
        "compiles_during_run": compiles["n"] - c0,
        "chunk_cache": len(eng._chunk_cache),
        "charge": [float(q) for q in eng.trace.values["charge"]],
        "halo_counts": dict(eng.halo_ledger.counts),
        "halo_bytes": dict(eng.halo_ledger.bytes),
        "halo_bytes_per_step": eng.halo_ledger.per_step_bytes(),
    }


def main():
    from repro.utils.compile_cache import use_compile_cache
    use_compile_cache()
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--cells", type=int, nargs=3, default=(8, 6, 6))
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--chunk", type=int, default=20)
    ap.add_argument("--kernel", action="store_true",
                    help="Pallas NEP evaluator through the sharded plan")
    args = ap.parse_args()
    res = run_engine_chunk(cells=tuple(args.cells), steps=args.steps,
                           chunk=args.chunk, kernel=args.kernel)
    print(f"engine chunk on {res['devices']} device(s): "
          f"{res['atoms']} atoms, grid {res['cells']}, "
          f"{res['steps_per_s']:.1f} steps/s, "
          f"{res['rebuilds']} in-scan rebuilds "
          f"({res['migrated']} migrations)")
    print(f"  halo ledger: {res['halo_counts']}")
    print(f"  Q trace: {[round(q, 2) for q in res['charge']]}")


if __name__ == "__main__":
    main()
