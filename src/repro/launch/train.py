"""Unified training/simulation driver.

  LM:  PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b \
          --steps 200 --batch 8 --seq 512 [--smoke] [--ckpt-dir ckpts]
  MD:  PYTHONPATH=src python -m repro.launch.train --arch fege-spinlattice \
          --steps 500 --cells 6 --temperature 160

Runs on whatever devices exist (1 CPU here; the production mesh via the
same sharding rules on a real slice).  Checkpoint/restart via --ckpt-dir:
kill and relaunch to resume from the newest complete checkpoint.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.ckpt.checkpoint import latest_step, load_checkpoint, \
    save_checkpoint
from repro.data.tokens import synthetic_batches
from repro.models import lm
from repro.train.optimizer import cosine_schedule
from repro.train.train_step import init_train_state, make_train_step


def train_lm(args, cfg_override=None):
    cfg = cfg_override or (configs.get_smoke(args.arch) if args.smoke
                           else configs.get(args.arch))
    key = jax.random.PRNGKey(args.seed)
    params = lm.init_params(cfg, key, tp=1)
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M")
    state = init_train_state(params)

    loss_fn = lm.make_loss_fn(cfg, remat=True, kv_chunk=min(args.seq, 512),
                              xent_chunk=512)
    step_fn = jax.jit(make_train_step(
        loss_fn,
        lambda s: cosine_schedule(s, peak_lr=args.lr, warmup=20,
                                  total=args.steps),
        accum=args.accum))

    start = 0
    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        state, start = load_checkpoint(args.ckpt_dir, state)
        start += 1
        print(f"resumed from step {start}")

    batches = synthetic_batches(cfg, args.batch, args.seq, args.seed)
    t0 = time.time()
    for i in range(start, args.steps):
        state, metrics = step_fn(state, next(batches))
        if i % args.log_every == 0 or i == args.steps - 1:
            dt = time.time() - t0
            tok_s = args.batch * args.seq * (i - start + 1) / max(dt, 1e-9)
            print(f"step {i:5d} loss {float(metrics['loss']):.4f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"tok/s {tok_s:.0f}")
        if args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
            save_checkpoint(args.ckpt_dir, i, state, async_=True)
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, args.steps - 1, state)
    return state


def train_md(args):
    """Spin-lattice production run (single-device path; the multi-device
    path is exercised by dryrun + tests/test_domain.py)."""
    from repro.core.descriptor import NEPSpinSpec
    from repro.core.hamiltonian import HeisenbergDMIModel
    from repro.core.training import generate_dataset, fit_adam, rmse_metrics
    from repro.md.lattice import b20_fege
    from repro.md.state import init_state, kinetic_energy, temperature_of
    from repro.md.integrator import IntegratorConfig
    from repro.md.simulate import Simulation
    from repro.md.analysis import helix_pitch, topological_charge

    jax.config.update("jax_enable_x64", True)
    key = jax.random.PRNGKey(args.seed)
    lat = b20_fege()
    oracle = HeisenbergDMIModel(r0=2.45, morse_de=0.4, morse_alpha=1.6,
                                d0=args.d_over_j * 0.0166)
    spec = NEPSpinSpec(l_max=2, n_ang=2, n_rad=4, n_spin=3, basis_size=6)

    print("generating synthetic constrained-DFT data + fitting NEP-SPIN...")
    ds = generate_dataset(oracle, lat, (2, 2, 2), 24, key)
    params, _ = fit_adam(spec, ds, key, steps=args.fit_steps)
    print("fit:", {k: float(v) for k, v in
                   rmse_metrics(spec, params, ds).items()})

    st = init_state(lat, (args.cells,) * 3, temperature=args.temperature,
                    spin_init="helix_x", key=key)

    class NEP:
        def energy_forces_field(self, pos, spin, types, table, box,
                                field=None):
            from repro.core.potential import energy_forces_field
            return energy_forces_field(spec, params, pos, spin, types,
                                       table, box, field,
                                       jnp.asarray(lat.moments))

    icfg = IntegratorConfig(dt=2e-3, temperature=args.temperature,
                            lattice_gamma=2.0, spin_alpha=0.05,
                            spin_longitudinal=0.05)
    sim = Simulation(potential=NEP(), cfg=icfg, state=st,
                     masses=jnp.asarray(lat.masses),
                     magnetic=jnp.asarray(lat.moments) > 0,
                     cutoff=spec.cutoff, capacity=64,
                     field=jnp.asarray([0.0, 0.0, args.field]))
    t0 = time.time()
    for chunk in range(args.steps // 50):
        sim.run(50, jax.random.fold_in(key, chunk), chunk=25)
        q = topological_charge(sim.state.pos, sim.state.spin, sim.state.box)
        print(f"step {(chunk+1)*50:5d} E {sim.energy:10.4f} "
              f"T {float(temperature_of(sim.state, jnp.asarray(lat.masses))):6.1f}K "
              f"Q {float(q):+.2f}  ({time.time()-t0:.0f}s)")
    print(f"pitch: {float(helix_pitch(sim.state.pos, sim.state.spin, sim.state.box)):.1f} A")
    return sim.state


def main():
    from repro.utils.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    # MD options
    ap.add_argument("--cells", type=int, default=6)
    ap.add_argument("--temperature", type=float, default=160.0)
    ap.add_argument("--field", type=float, default=0.1)
    ap.add_argument("--d-over-j", type=float, default=0.3)
    ap.add_argument("--fit-steps", type=int, default=150)
    args = ap.parse_args()
    if args.arch == "fege-spinlattice":
        train_md(args)
    else:
        train_lm(args)


if __name__ == "__main__":
    main()
