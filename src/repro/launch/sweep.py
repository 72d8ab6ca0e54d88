"""(T, B) phase-diagram sweep entrypoint.

  PYTHONPATH=src python -m repro.launch.sweep [--preset smoke|full]
      [--replicas R] [--steps N] [--temps 40,95] [--fields 0,25]

Fans replicas over the (T, B) grid through the vmapped ensemble engine
(repro.ensemble.sweep) on the reduced-scale strong-DMI film and prints the
filled PhaseDiagram: |Q| (skyrmion count scale), <S_z>, helix pitch per
grid cell - the helix -> skyrmion phase map of the paper's Figs. 4/9.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.fege_spinlattice import (nucleation_ensemble,
                                            nucleation_ensemble_smoke)
from repro.ensemble.sweep import run_sweep
from repro.md.integrator import IntegratorConfig
from repro.md.lattice import simple_cubic
from repro.md.state import init_state


def build_film(ecfg, seed: int = 0):
    """Reduced-scale strong-DMI film: helix ground state that fits the box."""
    from repro.core.hamiltonian import HeisenbergDMIModel
    lat = simple_cubic()
    d_over_j = float(np.tan(2 * np.pi / 8))   # 8-site textures
    ham = HeisenbergDMIModel(d0=0.0166 * d_over_j, gamma_j=0.0,
                             gamma_d=0.0, ka=0.0)
    st = init_state(lat, ecfg.n_cells, spin_init="helix_x",
                    helix_pitch=8 * lat.a, key=jax.random.PRNGKey(seed))
    return lat, ham, st


def main() -> None:
    from repro.utils.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--preset", default="smoke", choices=("smoke", "full"))
    ap.add_argument("--replicas", type=int, default=0)
    ap.add_argument("--steps", type=int, default=0)
    ap.add_argument("--temps", default="",
                    help="comma-separated T grid [K] (default: preset)")
    ap.add_argument("--fields", default="",
                    help="comma-separated B grid [T] (default: preset)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    ecfg = (nucleation_ensemble_smoke() if args.preset == "smoke"
            else nucleation_ensemble())
    n_rep = args.replicas or ecfg.n_replicas
    n_steps = args.steps or ecfg.n_steps
    temps = ([float(x) for x in args.temps.split(",")] if args.temps
             else list(ecfg.sweep_temperatures))
    fields = ([float(x) for x in args.fields.split(",")] if args.fields
              else list(ecfg.sweep_fields))

    lat, ham, st = build_film(ecfg, args.seed)
    cfg = IntegratorConfig(dt=ecfg.dt, lattice_gamma=ecfg.lattice_gamma,
                           spin_alpha=ecfg.spin_alpha)
    n_tot = len(temps) * len(fields) * n_rep
    print(f"sweep: {len(temps)}x{len(fields)} grid x {n_rep} replicas = "
          f"{n_tot} batched replicas, {st.n_atoms} atoms each, "
          f"{n_steps} steps")
    t0 = time.time()
    pd = run_sweep(
        st, ham, cfg, jnp.asarray(lat.masses),
        jnp.asarray(lat.moments) > 0, temps, fields,
        n_replicas=n_rep, n_steps=n_steps, key=jax.random.PRNGKey(args.seed),
        cutoff=5.0, capacity=8, chunk=ecfg.chunk)
    dt_wall = time.time() - t0
    print(f"\n{pd.summary()}")
    print(f"\n<S_z>:\n{np.array2string(pd.magnetization, precision=3)}")
    print(f"pitch [A]:\n{np.array2string(pd.pitch, precision=1)}")
    rate = n_tot * st.n_atoms * n_steps / dt_wall
    print(f"\n{dt_wall:.1f}s wall, {rate:.3e} atom-step/s aggregate")


if __name__ == "__main__":
    main()
