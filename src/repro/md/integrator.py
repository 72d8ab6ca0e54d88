"""Structure-preserving coupled spin-lattice integrator.

Suzuki-Trotter operator splitting in the style of Tranchida et al. (J. Comp.
Phys. 372, 406 (2018), the LAMMPS SPIN package) adapted per the paper:

    v(dt/2) -> S(dt/2) -> x(dt) -> recompute (F, H) -> S(dt/2) -> v(dt/2)

Spin updates are exact Rodrigues rotations about the local effective field
(norm-conserving by construction).  For strong feedback between the spin
state and the effective field the explicit rotation is replaced by the
paper's **self-consistent midpoint iteration** (Section 5-A3): repeatedly
form the midpoint configuration, re-evaluate the effective field there, and
re-apply the one-step rotation until convergence or an iteration cap, with
an optional regularized (damped) fixed-point acceleration.  Because this may
trigger several field re-evaluations per step, the spin update is scheduled
last among the half-step operations before/after the position drift, exactly
as the paper prescribes.

Thermostats (optional, for real-temperature dynamics):
  lattice - Langevin (exact OU velocity update),
  spin    - stochastic Landau-Lifshitz-Gilbert transverse noise with the
            fluctuation-dissipation variance 2 alpha kB T / (gamma mu dt),
            plus an optional longitudinal Landau channel for |S| fluctuations
            (the paper's "longitudinal fluctuation of magnetic moment").

Temperature and external field are **runtime inputs**: the built step
accepts optional ``temperature`` (scalar, K) and ``field`` ((3,), Tesla)
arguments so annealing / field-cooling protocols (repro.ensemble.protocol)
can drive a single compiled step through a whole schedule, and ``vmap`` can
batch replicas at different (T, B) points.  When omitted they fall back to
the compile-time ``IntegratorConfig`` constants (the pre-ensemble behavior,
bitwise compatible).

With damping = noise = 0 the scheme is time-reversible, conserves |S_i|
exactly and total energy to O(dt^2) (tested in tests/test_integrator.py).
"""
from __future__ import annotations

import dataclasses
import inspect
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.md.state import SpinLatticeState
from repro.utils import units


@dataclasses.dataclass(frozen=True)
class IntegratorConfig:
    dt: float = 1.0e-3            # ps
    # spin precession: dS/dt = -(gyro/(m mu_B)) S x (-dE/dS)
    moment: float = 1.16          # mu_B per magnetic atom
    # self-consistent midpoint spin update
    midpoint: bool = False
    midpoint_iters: int = 3
    midpoint_tol: float = 1e-10
    midpoint_mixing: float = 1.0  # <1 = regularized fixed point
    # thermostats (0 = off -> NVE, structure-preserving)
    temperature: float = 0.0      # K (default; runtime arg overrides)
    lattice_gamma: float = 0.0    # 1/ps Langevin friction
    spin_alpha: float = 0.0       # Gilbert damping
    spin_longitudinal: float = 0.0  # 1/ps longitudinal relaxation rate
    # frozen-lattice atomistic spin dynamics: the baseline method class the
    # paper positions against ("the lattice is often frozen or replaced by
    # a thermal bath", Sec. 4) - positions/velocities are not advanced
    frozen_lattice: bool = False


def force_calls_per_step(cfg: IntegratorConfig) -> int:
    """Force evaluations one fused step makes (:func:`make_fused_step`):
    one after the drift, plus ``midpoint_iters`` in each spin half-step
    when the self-consistent midpoint update is on."""
    return 1 + 2 * cfg.midpoint_iters if cfg.midpoint else 1


class ForceField(NamedTuple):
    """Output of one fused potential evaluation."""
    energy: jax.Array  # ()
    force: jax.Array   # (N,3) eV/A
    field: jax.Array   # (N,3) -dE/dS, eV


# potential evaluation signature: (pos, spin, field) -> ForceField, with
# field the external (3,) B-field in Tesla (None -> evaluator's own default).
# Legacy two-argument (pos, spin) evaluators are still accepted by
# ``make_step`` (the runtime field is then ignored by the potential).
EvalFn = Callable[..., ForceField]


def _rodrigues(s: jax.Array, omega: jax.Array, dt: float) -> jax.Array:
    """Rotate spins s about axis/angle omega*dt (exact, norm-conserving)."""
    theta = jnp.linalg.norm(omega, axis=-1, keepdims=True)
    # guard zero rotation
    axis = omega / jnp.where(theta > 0, theta, 1.0)
    ang = theta * dt
    c, si_ = jnp.cos(ang), jnp.sin(ang)
    return (s * c + jnp.cross(axis, s) * si_
            + axis * jnp.sum(axis * s, axis=-1, keepdims=True) * (1.0 - c))


def _precession_rate(field: jax.Array, spin: jax.Array, cfg: IntegratorConfig,
                     key: jax.Array | None, temp,
                     duration: float | None = None) -> jax.Array:
    """Angular velocity omega (N,3) [rad/ps] incl. damping + thermal noise.

    Landau-Lifshitz form: omega = g' (B + b_th) + g' alpha (S x B),
    with g' = gyro/(1+alpha^2) and B = field / (m mu_B) in Tesla.
    The thermal-field variance satisfies the fluctuation-dissipation
    relation <b^2> = 2 alpha kB T / (gyro mu tau) for the *applied kick
    duration tau* (each half-step draws an independent kick, so tau = dt/2
    there; validated by tests/test_integrator.py::test_single_spin_boltzmann
    against the Langevin function).  ``temp`` may be a traced scalar.
    """
    b = field / (cfg.moment * units.MU_B)  # Tesla
    tau = duration if duration is not None else cfg.dt
    if cfg.spin_alpha > 0.0 and key is not None:
        sigma = jnp.sqrt(2.0 * cfg.spin_alpha * units.KB * temp
                         / (units.GYRO * cfg.moment * units.MU_B * tau))
        b = b + sigma * jax.random.normal(key, b.shape, b.dtype)
    gp = units.GYRO / (1.0 + cfg.spin_alpha ** 2)
    omega = gp * b
    if cfg.spin_alpha > 0.0:
        omega = omega + gp * cfg.spin_alpha * jnp.cross(spin, b)
    return omega


def _spin_half_step(
    field_eval: Callable[[jax.Array], ForceField], spin: jax.Array,
    ff: ForceField, cfg: IntegratorConfig, key: jax.Array | None, temp,
) -> tuple[jax.Array, ForceField]:
    """Advance spins by dt/2; optionally self-consistent midpoint iteration.

    ``field_eval(spin) -> ForceField`` re-evaluates the potential at the
    *current positions* - in the fused path it closes over one pre-gathered
    :class:`~repro.md.neighbor.Neighborhood`, so every midpoint iteration
    reuses the same neighbor blocks instead of re-gathering.
    """
    half = 0.5 * cfg.dt

    def rotate(field, s0):
        omega = _precession_rate(field, s0, cfg, key, temp, duration=half)
        return _rodrigues(s0, omega, half)

    if not cfg.midpoint:
        return rotate(ff.field, spin), ff

    def body(carry, _):
        s_new, _ff = carry
        mid = 0.5 * (spin + s_new)
        # renormalize midpoint magnitude to the conserved |S| of the
        # transverse rotation (keeps the fixed point on the sphere)
        nrm = jnp.linalg.norm(spin, axis=-1, keepdims=True)
        mid = mid / jnp.maximum(jnp.linalg.norm(mid, axis=-1, keepdims=True),
                                1e-30) * nrm
        ff_mid = field_eval(mid)
        s_next = rotate(ff_mid.field, spin)
        if cfg.midpoint_mixing < 1.0:
            s_next = (cfg.midpoint_mixing * s_next
                      + (1.0 - cfg.midpoint_mixing) * s_new)
        return (s_next, ff_mid), jnp.max(jnp.abs(s_next - s_new))

    (s_fin, ff_fin), _resid = jax.lax.scan(
        body, (rotate(ff.field, spin), ff), None, length=cfg.midpoint_iters)
    return s_fin, ff_fin


def _longitudinal_step(spin: jax.Array, ff: ForceField,
                       cfg: IntegratorConfig, key: jax.Array | None, temp,
                       mag_mask: jax.Array) -> jax.Array:
    """Overdamped Langevin dynamics of |S| along s_hat (Landau channel)."""
    if cfg.spin_longitudinal <= 0.0:
        return spin
    nrm = jnp.linalg.norm(spin, axis=-1, keepdims=True)
    shat = spin / jnp.maximum(nrm, 1e-30)
    # force conjugate to |S|: f = (-dE/dS) . s_hat
    f_long = jnp.sum(ff.field * shat, axis=-1, keepdims=True)
    eta = cfg.spin_longitudinal
    dnrm = eta * cfg.dt * f_long
    if key is not None:
        dnrm = dnrm + jnp.sqrt(2.0 * eta * units.KB * temp
                               * cfg.dt) * jax.random.normal(
                                   key, nrm.shape, spin.dtype)
    new_nrm = jnp.maximum(nrm + dnrm, 1e-3)
    return jnp.where(mag_mask[..., None], shat * new_nrm, spin)


def _lattice_langevin(vel: jax.Array, masses: jax.Array,
                      cfg: IntegratorConfig, key: jax.Array,
                      temp) -> jax.Array:
    """Exact half-step Ornstein-Uhlenbeck velocity update (OBABO splitting)."""
    c1 = jnp.exp(-cfg.lattice_gamma * 0.5 * cfg.dt)
    sigma = jnp.sqrt(units.KB * temp * (1.0 - c1 ** 2)
                     / (masses * units.MVV2E))
    return c1 * vel + sigma[..., None] * jax.random.normal(key, vel.shape,
                                                           vel.dtype)


def _adapt_eval(evaluate: EvalFn) -> EvalFn:
    """Accept legacy (pos, spin) evaluators alongside (pos, spin, field).

    Field-aware evaluators must name their third parameter ``field`` (a
    bare arity check would misroute the field into closure-default params
    like ``evaluate(pos, spin, tab=tab)``)."""
    try:
        pars = list(inspect.signature(evaluate).parameters.values())
    except (TypeError, ValueError):  # builtins / exotic callables
        return evaluate
    if len(pars) >= 3 and pars[2].name == "field":
        return evaluate

    def ev(pos, spin, field):
        return evaluate(pos, spin)
    return ev


def make_fused_step(
    gather: Callable,           # (pos, nbh) -> nbh (refresh after drift)
    compute: Callable,          # (nbh, spin, types, field) -> ForceField
    cfg: IntegratorConfig,
    masses: jax.Array,          # (n_types,)
    magnetic: jax.Array,        # (n_types,) bool
    atom_mask: jax.Array | str | None = None,  # empty-slot mask (domain)
    spin_aware_gather: bool | None = None,     # None -> infer from arity
):
    """Build the gather-once coupled step:

        (state, ff, nbh, key[, temperature[, field]]) -> (state, ff, nbh)

    The step owns the neighbor-block lifecycle *within* a step: the incoming
    ``nbh`` (gathered at ``state.pos``) serves the first spin half-step and
    all of its midpoint iterations; after the position drift, ``gather``
    refreshes it exactly once and the refreshed block serves the force
    recompute, the second spin half-step (+ iterations), and the
    longitudinal channel.  Table rebuild remains the caller's responsibility
    (repro.md.simulate runs it in-scan behind a ``lax.cond``).

    ``temperature`` (scalar K) and ``field`` ((3,) Tesla) are optional
    runtime overrides of the ``IntegratorConfig`` constants; protocols and
    replica ensembles thread per-step / per-replica values through them.
    Works on flat (N, ...) arrays AND cell-blocked (CX,CY,CZ,K, ...) domain
    arrays (all updates are elementwise); ``atom_mask`` freezes empty
    slots.  In the fixed-capacity domain layout the occupancy changes when
    atoms migrate between cells, so ``atom_mask="from_types"`` derives the
    mask from ``state.types >= 0`` at every call instead of baking in an
    array (the sharded fused loop uses this; types == -1 marks empties).

    ``gather`` may accept a third ``spin`` argument: it is then called as
    ``gather(pos, nbh, spin)`` with the post-half-step spins, letting the
    distributed loop refresh neighbor-spin blocks in the SAME fused halo
    round as the position exchange (classical MD's one-message step).
    """
    if spin_aware_gather is not None:
        gather_takes_spin = spin_aware_gather
    else:
        try:
            gather_takes_spin = len(
                inspect.signature(gather).parameters) >= 3
        except (TypeError, ValueError):
            gather_takes_spin = False

    def step(state: SpinLatticeState, ff: ForceField, nbh, key: jax.Array,
             temperature=None, field=None):
        k1, k2, k3, k4, k5 = jax.random.split(key, 5)
        types_c = jnp.maximum(state.types, 0)
        m = masses[types_c][..., None]
        mag = magnetic[types_c]
        amask = (state.types >= 0 if isinstance(atom_mask, str)
                 else atom_mask)
        if amask is not None:
            mag = mag & amask
        dt = cfg.dt
        # `temperature is None` is a trace-time (static) condition: with no
        # runtime override the stochastic branches compile exactly as the
        # static-config integrator did.
        stochastic = (temperature is not None) or cfg.temperature > 0.0
        temp = cfg.temperature if temperature is None else \
            jnp.maximum(temperature, 0.0)

        def field_eval(nb):
            return lambda s: compute(nb, s, state.types, field)

        vel = state.vel
        vmask = (amask[..., None] if amask is not None else
                 jnp.ones_like(vel, dtype=bool))
        if not cfg.frozen_lattice:
            if cfg.lattice_gamma > 0.0 and stochastic:
                vel = jnp.where(vmask, _lattice_langevin(
                    vel, masses[types_c], cfg, k1, temp), vel)
            # B: half kick
            vel = vel + 0.5 * dt * ff.force / m * units.FORCE2ACC
        # spin half step (scheduled last among half-step ops: may re-evaluate)
        spin, ff = _spin_half_step(
            field_eval(nbh), state.spin, ff, cfg,
            k2 if stochastic else None, temp)
        spin = jnp.where(mag[..., None], spin, state.spin)
        # A: drift
        if cfg.frozen_lattice:
            pos = state.pos
        else:
            pos = state.pos + dt * vel
            pos = pos - state.box * jnp.floor(pos / state.box)  # wrap PBC
        # recompute at new positions: the ONE gather of this step (a
        # spin-aware gather also refreshes neighbor-spin blocks here - the
        # distributed loop fuses both into one halo exchange)
        nbh = gather(pos, nbh, spin) if gather_takes_spin else \
            gather(pos, nbh)
        ff = compute(nbh, spin, state.types, field)
        # spin half step
        spin2, ff = _spin_half_step(
            field_eval(nbh), spin, ff, cfg, k3 if stochastic else None, temp)
        spin = jnp.where(mag[..., None], spin2, spin)
        spin = _longitudinal_step(spin, ff, cfg,
                                  k4 if stochastic else None, temp, mag)
        if not cfg.frozen_lattice:
            # B: half kick
            vel = vel + 0.5 * dt * ff.force / m * units.FORCE2ACC
            if cfg.lattice_gamma > 0.0 and stochastic:
                vel = jnp.where(vmask, _lattice_langevin(
                    vel, masses[types_c], cfg, k5, temp), vel)

        return SpinLatticeState(pos=pos, vel=vel, spin=spin,
                                types=state.types, box=state.box,
                                step=state.step + 1), ff, nbh

    return step


def make_step(
    evaluate: EvalFn,
    cfg: IntegratorConfig,
    masses: jax.Array,          # (n_types,)
    magnetic: jax.Array,        # (n_types,) bool
    atom_mask: jax.Array | None = None,  # empty-slot mask (domain decomp)
):
    """Build the jit-able coupled step (un-split evaluation):

        (state, ff, key[, temperature[, field]]) -> (state, ff)

    ``evaluate`` must close over types/neighbor-table/box; it receives the
    runtime field as a third argument (legacy two-argument evaluators keep
    working and ignore it).  Implemented as :func:`make_fused_step` with the
    positions themselves standing in for the gathered blocks, which makes it
    graph-identical to the pre-fusion integrator.
    """
    ev = _adapt_eval(evaluate)
    fstep = make_fused_step(
        gather=lambda pos, _nbh: pos,
        compute=lambda nbh, spin, types, field: ev(nbh, spin, field),
        cfg=cfg, masses=masses, magnetic=magnetic, atom_mask=atom_mask)

    def step(state: SpinLatticeState, ff: ForceField, key: jax.Array,
             temperature=None, field=None):
        state, ff, _ = fstep(state, ff, state.pos, key, temperature, field)
        return state, ff

    return step
