"""Plain Heisenberg-DMI spin-lattice energy: Morse lattice, exchange J(r),
bulk DMI D(r) rhat . (S_i x S_j), pseudo-dipolar coupling, single-ion and
Landau longitudinal terms; every pair term under the smooth cosine cutoff
and split half to each atom.  Vectors are triples of (B, M) arrays."""
from __future__ import annotations

import jax.numpy as jnp

from bench.reference.forces import cross, dot


def site_moments(cfg: dict):
    p = cfg["params"]
    n_types = len(cfg["lattice"]["masses"])
    return [p["moment"] if t == p["magnetic_type"] else 0.0
            for t in range(n_types)]


def atom_energy(cfg: dict, w: dict, dtype):
    p = {k: (jnp.asarray(v, dtype) if k != "magnetic_type" else v)
         for k, v in w.items()}

    def f(dr, mask, ti, tj, si, sj):
        dr = tuple(x.astype(dtype) for x in dr)
        si = tuple(x.astype(dtype)[:, None] for x in si)
        sj = tuple(x.astype(dtype) for x in sj)
        dist = jnp.sqrt(dot(dr, dr) + 1e-30)
        fc = 0.5 * (1.0 + jnp.cos(jnp.pi * jnp.clip(dist / p["cutoff"],
                                                   0.0, 1.0)))
        fc = fc * mask.astype(dtype)
        rhat = tuple(c / dist for c in dr)
        dr0 = dist - p["r0"]
        lattice = p["morse_de"] * ((1.0 - jnp.exp(-p["morse_alpha"] * dr0))
                                   ** 2 - 1.0) * fc
        mag_i = (ti == p["magnetic_type"]).astype(dtype)
        mag = mag_i[:, None] * (tj == p["magnetic_type"]).astype(dtype)
        decay_j = jnp.exp(-p["gamma_j"] * dr0) * fc * mag
        exch = -p["j0"] * decay_j * dot(si, sj)
        dmi = -p["d0"] * jnp.exp(-p["gamma_d"] * dr0) * fc * mag * dot(
            rhat, cross(si, sj))
        pdip = p["kpd"] * decay_j * dot(si, rhat) * dot(sj, rhat)
        pair = 0.5 * jnp.sum(lattice + exch + dmi + pdip, axis=1)
        s2 = dot(si, si)[:, 0]
        axis = p["ka_axis"]
        along = si[0][:, 0] * axis[0] + si[1][:, 0] * axis[1] \
            + si[2][:, 0] * axis[2]
        onsite = (p["ka"] * along ** 2
                  + p["landau_a"] * (s2 - 1.0) ** 2) * mag_i
        return pair + onsite

    return f
