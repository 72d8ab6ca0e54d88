"""The system under test for a coupled spin-lattice cell: the seeded state,
the traffic's schedules and the program's :class:`repro.md.engine.Engine`
on the plan its configuration names: ``SingleDevice`` without a ``plan``
key, or ``{"kind": "sharded", "mesh": {"sx": 2, "sy": 2}, "axis_map":
["sx", "sy", null]}``, the ``Sharded`` domain decomposition over a mesh of
the first devices, its axes in that order, which resolves its own grid,
cell capacity and halo mode.

Inputs are made here from ``--seed`` alone (positions kicked off the
perfect crystal, Maxwell velocities, random spins); weights come from the
configuration's own ``weights_seed`` in the builder of its kind.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench import units, work


def seed_key(seed: int) -> jax.Array:
    """A raw threefry key from a seed of up to 64 bits (``PRNGKey(seed)``
    for seeds that fit 32 bits)."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return jnp.asarray(np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32))


def make_mesh(cfg: dict):
    """The device mesh of the configuration's plan, over the first
    devices; None for one device."""
    from jax.sharding import Mesh

    plan = cfg.get("plan")
    if plan is None:
        return None
    if plan.get("kind") != "sharded" or set(plan) != {"kind", "mesh",
                                                      "axis_map"}:
        raise ValueError("a plan is {'kind': 'sharded', 'mesh': ..., "
                         f"'axis_map': ...}}, not {plan}")
    names = tuple(plan["mesh"])
    shape = tuple(int(plan["mesh"][a]) for a in names)
    devs = jax.devices()[:int(np.prod(shape))]
    return Mesh(np.asarray(devs).reshape(shape), names)


def make_plan(cfg: dict, mesh, states):
    """The program's plan object.  A ``Sharded`` plan is pinned to one
    cell grid and capacity for every state of the pool (the grid that the
    program resolves for the first state, the largest capacity it resolves
    for any on that grid), so that every episode runs one compiled chunk."""
    from repro.parallel.plan import Sharded, SingleDevice

    spec = cfg.get("plan")
    if spec is None:
        return SingleDevice()
    plan = Sharded(mesh=mesh, axis_map=tuple(spec["axis_map"]))
    rc, skin = potential_cutoff(cfg), cfg["neighbor"]["skin"]
    k = 0
    for s in states:
        rp = plan.resolve(s.box, s.pos, rc, skin, s.pos.dtype == jnp.float32)
        plan = dataclasses.replace(plan, cells=rp.dspec.cells)
        k = max(k, rp.dspec.capacity)
    return dataclasses.replace(plan, cell_capacity=k)


def make_state(cfg: dict, traffic: dict, seed: int):
    """Seeded initial state, made on the device in one jitted call.  On
    one device it is committed to the first device (so every episode
    restart hands the engine the same arrays); a decomposed plan bins it
    into its mesh itself."""
    from repro.md.state import SpinLatticeState

    lat, init = cfg["lattice"], traffic["initial"]
    pos0, types, box = work.lattice_sites(lat, cfg["cells"])
    masses = np.asarray(lat["masses"])[types]
    magnetic = np.asarray(lat["moments"])[types] > 0
    sigma = np.sqrt(units.KB * init["temperature_K"] / (masses * units.MVV2E))
    if init["spin_init"] != "random":
        raise ValueError(f"unknown spin_init {init['spin_init']!r}")

    def build(key):
        k_vel, k_spin, k_pos = jax.random.split(key, 3)
        n = pos0.shape[0]
        vel = jnp.asarray(sigma, jnp.float32)[:, None] * jax.random.normal(
            k_vel, (n, 3), jnp.float32)
        vel = vel - jnp.mean(vel, axis=0, keepdims=True)
        s = jax.random.normal(k_spin, (n, 3), jnp.float32)
        s = s / jnp.linalg.norm(s, axis=-1, keepdims=True)
        spin = jnp.where(jnp.asarray(magnetic)[:, None], s, 0.0)
        b = jnp.asarray(box, jnp.float32)
        pos = jnp.asarray(pos0, jnp.float32) + init["displace_A"] * \
            jax.random.normal(k_pos, (n, 3), jnp.float32)
        pos = pos - b * jnp.floor(pos / b)
        return pos, vel, spin

    pos, vel, spin = jax.jit(build)(seed_key(seed))
    state = SpinLatticeState(pos=pos, vel=vel, spin=spin,
                             types=jnp.asarray(types, jnp.int32),
                             box=jnp.asarray(box, jnp.float32),
                             step=jnp.asarray(0, jnp.int32))
    if cfg.get("plan") is None:
        state = jax.device_put(state, jax.devices()[0])
    return jax.block_until_ready(state)


def schedule_values(traffic: dict, t_ps: np.ndarray):
    """The traffic's (temperature [K], field [T]) at times ``t_ps``,
    evaluated in plain numpy (the references' copy of the protocol)."""
    s = traffic["schedule"]
    if s["kind"] != "field_cooling":
        raise ValueError(f"unknown schedule kind {s['kind']!r}")
    knots = [0.0, s["t_hold_ps"], s["t_hold_ps"] + s["t_ramp_ps"]]
    temp = np.interp(t_ps, knots, [s["t_hot_K"], s["t_hot_K"], s["t_cold_K"]])
    field = np.broadcast_to(np.asarray(s["b_field_T"], np.float64),
                            np.shape(t_ps) + (3,))
    return temp, field


def make_schedules(traffic: dict):
    """The traffic's schedules as the program's protocol objects (lowered
    per chunk by the engine)."""
    from repro.ensemble import protocol

    s = traffic["schedule"]
    if s["kind"] != "field_cooling":
        raise ValueError(f"unknown schedule kind {s['kind']!r}")
    return protocol.field_cooling(s["t_hot_K"], s["t_cold_K"],
                                  np.asarray(s["b_field_T"], np.float32),
                                  t_hold=s["t_hold_ps"], t_ramp=s["t_ramp_ps"])


def make_engine(cfg: dict, traffic: dict, potential, state, plan):
    """The program's engine for this cell on ``plan`` (:func:`make_plan`);
    construction runs the first table build and force evaluation as one
    compiled program."""
    from repro.md.engine import Engine
    from repro.md.integrator import IntegratorConfig

    lat, nb = cfg["lattice"], cfg["neighbor"]
    temp, field = make_schedules(traffic)
    icfg = IntegratorConfig(dt=cfg["dt_ps"], moment=cfg["spin_moment"],
                            **traffic["integrator"])
    return Engine(
        potential=potential, cfg=icfg, state=state,
        masses=jnp.asarray(lat["masses"], jnp.float32),
        magnetic=jnp.asarray(lat["moments"]) > 0,
        cutoff=potential_cutoff(cfg), capacity=nb["capacity"],
        skin=nb["skin"], use_cell_list=True,
        cell_capacity=nb["cell_capacity"], plan=plan,
        temperature=temp, field=field,
        observables=tuple(traffic["observables"]))


def potential_cutoff(cfg: dict) -> float:
    """The cutoff of the configuration's potential [Å]."""
    return float((cfg.get("spec") or cfg.get("params"))["cutoff"])
