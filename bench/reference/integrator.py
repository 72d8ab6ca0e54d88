"""Plain coupled spin-lattice step (OBABO with Rodrigues spin rotations).

Per step, with keys k1..k5 split from the step key: lattice Langevin
half-step (k1), half kick, spin half-step about H_eff with the stochastic
LLG field (k2), drift, new (E, F, H_eff), spin half-step (k3), half kick,
lattice Langevin half-step (k5).  Random numbers are drawn per row of the
program's atom layout, so the layout (``perm``: row -> atom) is an input.

On a domain-decomposed layout (``blocks = (devices, slot_shape)``) each
device draws over its own block of cell slots, of ``slot_shape``, from the
step key folded with its linear device index, and only then splits k1..k5;
``perm`` lists the atom of every slot, device after device, -1 where the
slot is empty.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import units


class Step:
    def __init__(self, masses, magnetic, types, box, dt, moment,
                 lattice_gamma, spin_alpha, blocks=None):
        types = np.asarray(types)
        m = jnp.asarray(np.asarray(masses, np.float32)[types])
        mag = jnp.asarray(np.asarray(magnetic, bool)[types])[:, None]
        box = jnp.asarray(box, jnp.float32)
        half = 0.5 * dt
        c1 = float(np.exp(-lattice_gamma * half))
        gp = units.GYRO / (1.0 + spin_alpha ** 2)

        def noise(key, which, perm, like):
            """Normal numbers of key ``which`` of the step's five, in atom
            order."""
            if blocks is None:
                k = jax.random.split(key, 5)[which]
                z = jax.random.normal(k, like.shape, like.dtype)
                return jnp.zeros_like(z).at[perm].set(z)
            devices, shape = blocks
            z = jnp.concatenate([jax.random.normal(
                jax.random.split(jax.random.fold_in(key, d), 5)[which],
                tuple(shape) + like.shape[1:], like.dtype).reshape(
                    -1, like.shape[1]) for d in range(devices)])
            rows = jnp.where(perm >= 0, perm, like.shape[0])
            return jnp.zeros_like(like).at[rows].set(z, mode="drop")

        def thermostat(vel, key, which, perm, temp):
            sigma = jnp.sqrt(units.KB * temp * (1.0 - c1 ** 2)
                             / (m * units.MVV2E))
            return c1 * vel + sigma[:, None] * noise(key, which, perm, vel)

        def spin_half(spin, heff, key, which, perm, temp):
            b = heff / (moment * units.MU_B)
            sig = jnp.sqrt(2.0 * spin_alpha * units.KB * temp
                           / (units.GYRO * moment * units.MU_B * half))
            b = b + sig * noise(key, which, perm, b)
            omega = gp * b + gp * spin_alpha * jnp.cross(spin, b)
            theta = jnp.linalg.norm(omega, axis=-1, keepdims=True)
            axis = omega / jnp.where(theta > 0, theta, 1.0)
            c, s = jnp.cos(theta * half), jnp.sin(theta * half)
            new = (spin * c + jnp.cross(axis, spin) * s
                   + axis * jnp.sum(axis * spin, axis=-1, keepdims=True)
                   * (1.0 - c))
            return jnp.where(mag, new, spin)

        def before(pos, vel, spin, force, heff, key, perm, temp):
            temp = jnp.maximum(temp, 0.0)
            vel = thermostat(vel, key, 0, perm, temp)
            vel = vel + half * force / m[:, None] * units.FORCE2ACC
            spin = spin_half(spin, heff, key, 1, perm, temp)
            pos = pos + dt * vel
            return pos - box * jnp.floor(pos / box), vel, spin

        def after(vel, spin, force, heff, key, perm, temp):
            temp = jnp.maximum(temp, 0.0)
            spin = spin_half(spin, heff, key, 2, perm, temp)
            vel = vel + half * force / m[:, None] * units.FORCE2ACC
            return thermostat(vel, key, 4, perm, temp), spin

        def moved(pos, r0, limit):
            d = pos - r0
            d = d - box * jnp.round(d / box)
            return jnp.max(jnp.sum(d * d, axis=-1)) > limit * limit

        self.before = jax.jit(before)
        self.after = jax.jit(after)
        self.moved = jax.jit(moved)
